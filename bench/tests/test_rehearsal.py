"""Each cell end to end on the CPU at a tiny size: it runs through its
entry, the comparison passes, the lower-precision control fails it, and
every fault the cell can have, planted in the program, fails it too."""

import time

import jax
import jax.numpy as jnp
import pytest

import harness

TINY_FRAME = {"height": 32, "width": 32, "n": 1024, "m": 512}
CELLS = {
    "deblur_sec7_1024.serve_stratified": {
        "config": TINY_FRAME,
        "traffic": {"rate_per_s": 4.0, "lead_in_s": 1.0, "check_requests": 6,
                    "drain_limit_s": 30.0}},
}
SECONDS = 2.0


def run(cell, control=False, seed=20231117):
    return harness.run_cell(cell, seed, SECONDS, False, t_start=time.perf_counter(),
                            devices=jax.devices(), overrides=CELLS[cell], control=control)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_compares_correct(cell):
    r = run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert "setup_s" in r["metrics"] and len(r["metrics"]) >= 2
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", CELLS)
def test_lower_precision_control_fails(cell):
    r = run(cell, control=True)
    assert not r["correct"], r["checks"]


def _state_unchanged(monkeypatch):
    import repro.core.kernel_backend as kb

    monkeypatch.setattr(kb, "cpadmm_step_pallas", lambda op, const, state, p, **kw: state)


def _half_batch(monkeypatch):
    """Rows in the second half of every batch are never stepped."""
    import repro.core.kernel_backend as kb

    step = kb.cpadmm_step_pallas

    def half(op, const, state, p, **kw):
        new = step(op, const, state, p, **kw)
        rows = state.x.shape[0]
        keep = (jnp.arange(rows) < rows // 2)[:, None]
        return jax.tree.map(lambda a, b: jnp.where(keep, a, b), new, state)

    monkeypatch.setattr(kb, "cpadmm_step_pallas", half)


def _flip(x):
    """The largest entry of an answer, negated."""
    x = jnp.asarray(x)
    i = jnp.argmax(jnp.abs(x.reshape(-1)))
    return x.reshape(-1).at[i].multiply(-1.0).reshape(x.shape)


def _answer_altered(monkeypatch):
    import dataclasses

    import repro.serve.server as server

    step = server.RecoveryServer.step

    def altered_step(self):
        out = step(self)
        return [dataclasses.replace(r, x=_flip(r.x)) for r in out]

    monkeypatch.setattr(server.RecoveryServer, "step", altered_step)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "answer_altered": _answer_altered}
CASES = [(c, f) for c in CELLS for f in FAULTS]


@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_in_the_timed_path_fails(cell, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    r = run(cell)
    assert not r["correct"], r["checks"]


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(harness.BENCH, "run.py"),
                        "--workload", "deblur_sec7_1024.serve_stratified", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=harness.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

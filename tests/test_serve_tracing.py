"""The serving layer's own measurement: its profiler spans, its counters and
the named scopes of the round program it compiles.

Spans are recorded by a stand-in for ``jax.profiler.TraceAnnotation``
patched into the serve modules, so no profiler runs; every count is checked
against what the requests and the arrays read say it must be.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

import repro.serve.engine as engine_mod
import repro.serve.server as server_mod
from repro.core import partial_gaussian_circulant
from repro.data.synthetic import paper_regime
from repro.ops import PlanConfig, plan
from repro.serve import (
    BatchEngine,
    ManualClock,
    RecoveryServer,
    synthetic_workload,
)

N = 128
RHO = 0.01
SLOTS = 2


class Spans:
    """Records each span as (enclosing span names, name, arguments)."""

    def __init__(self):
        self.log, self._open = [], []

    def __call__(self, name, **args):
        spans = self

        class _Span:
            def __enter__(self):
                spans.log.append((tuple(spans._open), name, args))
                spans._open.append(name)

            def __exit__(self, *exc):
                spans._open.pop()

        return _Span()


@pytest.fixture
def spans(monkeypatch):
    rec = Spans()
    monkeypatch.setattr(engine_mod, "TraceAnnotation", rec)
    monkeypatch.setattr(server_mod, "TraceAnnotation", rec)
    return rec


def _op(seed=1):
    m, _ = paper_regime(N)
    return partial_gaussian_circulant(jax.random.PRNGKey(seed), N, m, normalize=True)


def _requests(op, count=5):
    return synthetic_workload(op, count, rate=1000.0, seed=7, tols=(1e-3, 1e-5),
                              max_iters=600)


def _server():
    return RecoveryServer(slots=SLOTS, round_iters=16, rho=RHO, sigma=RHO,
                          clock=ManualClock())


def _serve(srv, reqs, each_step=None, warm=True):
    """Warm up, submit every request, then step on a clock that moves a
    quarter second a round until all are resolved."""
    if warm:
        srv.warmup(reqs[0])
    for r in reqs:
        srv.submit(r)
    srv.clock.advance_to(max(r.arrival_time for r in reqs))
    while srv.pending or srv.busy:
        srv.step()
        if each_step is not None:
            each_step()
        srv.clock.tick(0.25)
    return srv.results


def _engine(srv):
    (eng,) = srv.engines.values()
    return eng


# -- spans -----------------------------------------------------------------
def test_spans_nest_in_each_scheduling_round(spans):
    srv = _server()
    reqs = _requests(_op())
    _serve(srv, reqs)
    log = [e for e in spans.log if "serve.step" in e[0] or e[1] == "serve.step"]
    steps = [i for i, (parents, name, _) in enumerate(log) if name == "serve.step"]
    assert steps and all(log[i][0] == () for i in steps)
    for a, b in zip(steps, steps[1:] + [len(log)]):
        names = [name for _, name, _ in log[a + 1:b]]
        parents = [p for p, _, _ in log[a + 1:b]]
        # one bucket: the admissions, the round, then the harvest
        rest = [n for n in names if n != "serve.admit"]
        assert rest[:2] == ["serve.round", "serve.round.wait"]
        assert rest[2:4] == ["serve.harvest", "serve.harvest.poll"]
        assert rest[4:] in ([], ["serve.harvest.fetch"])
        admits = names[:names.index("serve.round")]
        assert set(admits) <= {"serve.admit"}
        for p, n in zip(parents, names):
            want = {"serve.round.wait": ("serve.step", "serve.round"),
                    "serve.harvest.poll": ("serve.step", "serve.harvest"),
                    "serve.harvest.fetch": ("serve.step", "serve.harvest")}
            assert p == want.get(n, ("serve.step",)), (n, p)


def test_spans_follow_each_request_from_admission_to_harvest(spans):
    srv = _server()
    reqs = _requests(_op())
    results = _serve(srv, reqs)
    admitted = [a["request_id"] for p, n, a in spans.log
                if n == "serve.admit" and "serve.step" in p]
    assert sorted(admitted) == sorted(r.request_id for r in reqs)
    fetched = []
    for p, n, a in spans.log:
        if n == "serve.harvest.fetch" and "serve.step" in p:
            ids = a["request_id"].split(",")
            assert a["lanes"] == len(ids) >= 1
            fetched += ids
    assert sorted(fetched) == sorted(r.request_id for r in results)


# -- counters --------------------------------------------------------------
def _old_slot_iters(monkeypatch):
    """The count ``run_round`` used to take: the sum of ``age`` read before
    and after each round."""
    count = {"slot_iters": 0}
    run_round = BatchEngine.run_round

    def counted(self):
        if self.busy:
            before = int(jnp.sum(self._u.age))
            run_round(self)
            count["slot_iters"] += int(jnp.sum(self._u.age)) - before
        else:
            run_round(self)

    monkeypatch.setattr(BatchEngine, "run_round", counted)
    return count


def test_slot_iters_counts_harvested_iterations_as_before(monkeypatch):
    srv = _server()
    reqs = _requests(_op())
    old = _old_slot_iters(monkeypatch)
    seen = []

    def each_step():
        seen.append((_engine(srv).stats["slot_iters"], old["slot_iters"]))

    srv.warmup(reqs[0])
    old["slot_iters"] = 0  # warmup resets the engine's counters too
    results = _serve(srv, reqs, each_step, warm=False)
    stats = _engine(srv).stats
    assert stats["slot_iters"] == sum(r.iterations for r in results)
    assert stats["slot_iters"] == old["slot_iters"] > 0
    # equal after every scheduling round, not only at the end
    assert all(new == prev for new, prev in seen)
    assert stats["harvested"] == len(results) == len(reqs)
    assert stats["rounds"] > 0


def test_queue_wait_sums_admission_minus_arrival():
    srv = _server()
    results = _serve(srv, _requests(_op()))
    want = sum(r.admitted_time - r.arrival_time for r in results)
    assert want > 0  # two slots, five requests: some waited
    assert _engine(srv).stats["queue_wait_s"] == pytest.approx(want, rel=1e-12)
    assert _engine(srv).stats["admitted"] == len(results)


def test_waiting_counts_the_queue_wait_admitted_or_not():
    """``queue_wait_s + waiting_s`` grows by the queue's length for each
    second that passes: requests still queued count as far as they waited."""
    srv = _server()
    reqs = _requests(_op())
    srv.warmup(reqs[0])
    for r in reqs:
        srv.submit(r)
    t0 = max(r.arrival_time for r in reqs)
    srv.clock.advance_to(t0)

    def accrued():
        total = srv.stats()["total"]
        return total["queue_wait_s"] + total["waiting_s"]

    want = sum(t0 - r.arrival_time for r in reqs)
    assert srv.stats()["total"]["waiting_s"] == pytest.approx(want, rel=1e-12)
    while srv.pending:
        before, queued = accrued(), srv.pending
        srv.clock.tick(0.25)
        assert accrued() == pytest.approx(before + 0.25 * queued, rel=1e-12)
        before = accrued()
        srv.step()  # admission moves a request's wait, it adds none
        assert accrued() == pytest.approx(before, rel=1e-12)


def test_d2h_counts_every_array_harvest_reads(monkeypatch):
    srv = _server()
    reqs = _requests(_op())
    srv.warmup(reqs[0])
    reads = []
    device_get = jax.device_get

    def recorded(x):
        out = device_get(x)
        reads.append(out.nbytes)
        return out

    monkeypatch.setattr(jax, "device_get", recorded)
    _serve(srv, reqs, warm=False)
    stats = _engine(srv).stats
    assert stats["d2h_bytes"] == sum(reads) > 0
    # each poll reads five slot vectors; each fetch every lane's iterate
    fetch = SLOTS * N * 4
    polls = (sum(reads) - fetch * reads.count(fetch)) / (5 * SLOTS * 4)
    assert polls == stats["rounds"]


def test_run_round_reads_nothing_back(monkeypatch):
    from jax._src.array import ArrayImpl

    srv = _server()
    reqs = _requests(_op(), count=2)
    srv.warmup(reqs[0])
    for r in reqs:
        srv.submit(r)
    srv.step()  # admits both, so the next round has work
    eng = _engine(srv)
    reads = []
    value = ArrayImpl._value

    def counted(self):
        reads.append(self.shape)
        return value.fget(self)

    monkeypatch.setattr(ArrayImpl, "_value", property(counted))
    eng.run_round()
    assert eng.busy and reads == []


def test_rearm_and_round_write_into_the_donated_carry():
    srv = _server()
    reqs = _requests(_op(), count=2)
    srv.warmup(reqs[0])
    eng = _engine(srv)
    init = jax.tree.leaves(eng._u_init)
    carry = jax.tree.leaves(eng._u)
    eng.admit(0, reqs[0], 0.0)
    assert all(a.is_deleted() for a in carry)
    carry = jax.tree.leaves(eng._u)
    eng.run_round()
    assert all(a.is_deleted() for a in carry)
    assert not any(a.is_deleted() for a in init + jax.tree.leaves(eng._u))
    assert eng.harvest(0.0) == [] and int(eng._age[0]) == 16


def test_server_total_sums_every_engine_counter():
    srv = _server()
    a, b = _requests(_op(1), 3), _requests(_op(2), 3)
    b = [dataclasses.replace(r, request_id="b" + r.request_id) for r in b]
    results = _serve(srv, a + b)
    st = srv.stats()
    assert st["buckets"] == 2
    keys = set(next(iter(srv.engines.values())).stats)
    assert {"queue_wait_s", "harvested", "d2h_bytes"} <= keys
    assert set(st["total"]) == keys | {"waiting_s"}
    assert st["total"]["waiting_s"] == 0  # nothing left queued
    for k in keys:
        assert st["total"][k] == pytest.approx(sum(s[k] for s in st["per_bucket"].values()))
    assert st["total"]["harvested"] == len(results) == 6


# -- the round program -----------------------------------------------------
@pytest.mark.parametrize("tail", ["pallas", "jnp"])
def test_round_program_carries_the_solver_scopes(tail):
    op = _op()
    eng = BatchEngine(op, plan(op, None, config=PlanConfig(tail=tail)), slots=SLOTS,
                      round_iters=4, rho=RHO, sigma=RHO)
    text = eng.lower_round().compile().as_text()
    scopes = set(re.findall(r'op_name="([^"]*)"', text))
    for name in ("transform", "cpadmm.xupdate", "cpadmm.cx", "cpadmm.tail",
                 "until.test", "until.freeze"):
        assert any(f"/{name}/" in s for s in scopes), name
    assert any("round_fn" in s for s in scopes)

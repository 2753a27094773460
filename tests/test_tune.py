"""Plan autotuner (repro.ops.tune): ISSUE 6's tentpole contract.

  * Cache round-trip determinism — a warm cache hit returns the
    bit-identical config with *zero* scoring or measurement (counters).
  * Cost-model ranking sanity — rfft beats full-complex at n = 4096^2, the
    case PR 2 measured at 1.98x lower wire bytes.
  * Pins collapse the candidate space; the single validation site rejects
    bad inputs the same way at every entry point.

The 8-device tuned-vs-untuned solve equivalence lives in
tests/dist_progs/autotune_prog.py (slow lane).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import RecoveryProblem, solve
from repro.core.circulant import PartialCirculant, gaussian_circulant
from repro.data.synthetic import paper_regime, sparse_signal
from repro.dist.compat import make_mesh
from repro.ops import PlanConfig, plan, tune

N1, N2 = 32, 16
N = N1 * N2


@pytest.fixture(autouse=True)
def _fresh_counters():
    tune.reset_counters()
    yield


@pytest.fixture(autouse=True)
def _score_cpu_mesh_as_v5e(monkeypatch):
    """The tuner scores with the peak rates of its mesh's device kind and
    refuses kinds it has no entry for; these CPU meshes are scored with
    the v5e entry, named here explicitly."""
    from repro.launch import roofline

    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(roofline.PEAKS, kind, roofline.PEAKS[roofline.V5E])


@pytest.fixture
def cache(tmp_path):
    return tune.PlanCache(str(tmp_path / "plan_cache.json"))


def _problem(batch=()):
    x = sparse_signal(jax.random.PRNGKey(0), N, paper_regime(N)[1], batch=batch)
    C = gaussian_circulant(jax.random.PRNGKey(1), N, normalize=True)
    m = paper_regime(N)[0]
    omega = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), N)[:m])
    op = PartialCirculant(C, omega.astype(jnp.int32))
    return RecoveryProblem(op=op, y=op.matvec(x), x_true=x)


# ---------------------------------------------------------------------------
# cache: round-trip determinism, warm hits skip everything
# ---------------------------------------------------------------------------


def test_warm_cache_hit_skips_all_scoring_and_is_bit_identical(cache):
    op = _problem().op
    mesh = make_mesh((1,), ("model",))
    cfg1 = tune.tuned_config(op, mesh, batch=2, cache=cache)
    assert tune.COUNTERS["cache_misses"] == 1
    assert tune.COUNTERS["scored"] > 0
    tune.reset_counters()
    cfg2 = tune.tuned_config(op, mesh, batch=2, cache=cache)
    assert cfg2 == cfg1  # frozen dataclass equality = field-wise identity
    assert tune.COUNTERS == {
        "scored": 0, "measured": 0, "cache_hits": 1, "cache_misses": 0,
    }


def test_config_json_round_trip_is_lossless(cache):
    cfg = PlanConfig(rfft=True, overlap=4, tail="pallas", fused=False,
                     batch_axis=("pod", "data"), n1=64, n2=128)
    assert PlanConfig.from_dict(cfg.to_dict()) == cfg
    # and through the store itself
    cache.put("k", {"config": cfg.to_dict(), "mode": "model"})
    assert PlanConfig.from_dict(cache.get("k")["config"]) == cfg


def test_model_entry_does_not_satisfy_measure_request(cache):
    op = _problem().op
    mesh = make_mesh((1,), ("model",))
    tune.tuned_config(op, mesh, mode="model", batch=2, cache=cache)
    tune.reset_counters()
    tune.tuned_config(op, mesh, mode="measure", batch=2, cache=cache)
    assert tune.COUNTERS["cache_misses"] == 1
    assert tune.COUNTERS["measured"] > 0
    # ...but a measure entry satisfies both modes
    tune.reset_counters()
    tune.tuned_config(op, mesh, mode="model", batch=2, cache=cache)
    tune.tuned_config(op, mesh, mode="measure", batch=2, cache=cache)
    assert tune.COUNTERS["cache_hits"] == 2 and tune.COUNTERS["scored"] == 0


def test_pins_are_part_of_the_cache_key(cache):
    op = _problem().op
    mesh = make_mesh((1,), ("model",))
    k_free = tune.cache_key(op, mesh, 2, {})
    k_pin = tune.cache_key(op, mesh, 2, {"rfft": True})
    assert k_free != k_pin
    cfg = tune.tuned_config(op, mesh, batch=2, cache=cache,
                            pins={"rfft": False})
    assert cfg.rfft is False  # the pin survives into the winner


# ---------------------------------------------------------------------------
# cost-model ranking sanity
# ---------------------------------------------------------------------------


def test_rfft_beats_full_complex_at_4096_squared():
    """PR 2 measured the half-spectrum path at ~2x lower FFT flops and wire
    bytes per signal; the model must rank it first at the production size."""
    mesh = make_mesh((1,), ("model",))
    cands = [
        PlanConfig(rfft=False, n1=4096, n2=4096),
        PlanConfig(rfft=True, n1=4096, n2=4096),
    ]
    scored = tune.score_candidates(mesh, cands, batch=1, iters=2)
    assert scored[0][1].rfft is True
    assert scored[0][0] < scored[1][0]
    assert tune.COUNTERS["scored"] == 2


def test_overlap_sweep_shares_one_compile():
    mesh = make_mesh((1,), ("model",))
    cands = [
        PlanConfig(rfft=True, overlap=K, n1=N1, n2=N2) for K in (1, 2, 4, 8)
    ]
    scored = tune.score_candidates(mesh, cands, batch=1, iters=2)
    assert len(scored) == 4
    assert tune.COUNTERS["scored"] == 1  # one compile group, analytic K sweep
    # on a 1-device axis collectives vanish: ties break toward overlap=1
    assert scored[0][1].overlap == 1


# ---------------------------------------------------------------------------
# candidate space + pins
# ---------------------------------------------------------------------------


def test_candidate_configs_honor_pins():
    op = _problem().op
    mesh = make_mesh((1,), ("model",))
    free = tune.candidate_configs(op, mesh)
    assert {c.rfft for c in free} == {False, True}
    assert {c.overlap for c in free} == set(tune.OVERLAPS)
    pinned = tune.candidate_configs(op, mesh, pins={"rfft": True, "overlap": 2})
    assert all(c.rfft and c.overlap == 2 for c in pinned)
    n1_pinned = tune.candidate_configs(op, mesh, pins={"n1": 16})
    assert all(c.n1 == 16 and c.n2 == N // 16 for c in n1_pinned)


def test_candidate_configs_reject_unknown_axis():
    op = _problem().op
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="axis_name"):
        tune.candidate_configs(op, mesh, pins={"axis_name": "pod"})


def test_extra_factorizations_filtered_by_divisibility():
    op = _problem().op
    mesh = make_mesh((1,), ("model",))
    cands = tune.candidate_configs(
        op, mesh, pins={"rfft": True, "overlap": 1},
        extra_factorizations=[(N1, N2), (7, 11)],  # (7,11) != N: dropped
    )
    facs = {(c.n1, c.n2) for c in cands}
    assert (N1, N2) in facs and (7, 11) not in facs


# ---------------------------------------------------------------------------
# entry-point plumbing
# ---------------------------------------------------------------------------


def test_plan_tune_rejects_full_config():
    op = _problem().op
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="mutually exclusive"):
        plan(op, mesh, config=PlanConfig(), tune=True)


def test_tuned_config_rejects_unknown_mode():
    with pytest.raises(ValueError, match="model.*measure"):
        tune.tuned_config(None, None, mode="guess")


def test_local_tune_is_the_pins(cache):
    cfg = tune.tuned_config(_problem().op, None, pins={"tail": "pallas"})
    assert cfg == PlanConfig(tail="pallas")
    assert tune.COUNTERS["scored"] == 0  # nothing distributed to score


def test_measure_mode_plan_solves_correctly(cache):
    """End-to-end: a measure-tuned plan drives the same solve the default
    plan does (1-device fast-lane version of autotune_prog.py)."""
    prob = _problem(batch=(2,))
    mesh = make_mesh((1,), ("model",))
    pl = plan(prob.op, mesh, tune="measure", batch=2,
              tune_opts={"cache": cache})
    assert tune.COUNTERS["measured"] > 0
    x_ref, _ = solve(prob, "cpadmm", iters=150, record_every=150,
                     alpha=1e-4, rho=0.01, sigma=0.01)
    x_tuned, _ = solve(prob, "cpadmm", iters=150, record_every=150,
                       alpha=1e-4, rho=0.01, sigma=0.01, plan=pl)
    rel = float(jnp.linalg.norm(x_tuned - x_ref)
                / (jnp.linalg.norm(x_ref) + 1e-30))
    # re-knobbing is exact; a demoted wire (the timer may pick bf16) is
    # bounded by the plan layer's precision guard instead
    from repro.ops.plan import WIRE_ERROR_BOUND

    tol = 1e-5 if pl.wire_dtype == "fp32" else WIRE_ERROR_BOUND
    assert rel <= tol, (rel, pl.config.describe())
    # the cached winner rebuilds the identical plan config
    pl2 = plan(prob.op, mesh, tune="measure", batch=2,
               tune_opts={"cache": cache})
    assert pl2.config == pl.config


def test_cache_cli_show_and_clear(cache, capsys):
    op = _problem().op
    mesh = make_mesh((1,), ("model",))
    tune.tuned_config(op, mesh, batch=1, cache=cache)
    tune.main(["--cache", cache.path, "--show"])
    out = capsys.readouterr().out
    assert "1 cached plan" in out and "[model]" in out
    tune.main(["--cache", cache.path, "--clear"])
    assert cache.entries() == {}


def test_group_key_ignores_overlap_only():
    a = PlanConfig(rfft=True, overlap=1, n1=8, n2=8)
    b = dataclasses.replace(a, overlap=8)
    c = dataclasses.replace(a, rfft=False)
    assert tune._group_key(a) == tune._group_key(b)
    assert tune._group_key(a) != tune._group_key(c)


# ---------------------------------------------------------------------------
# cache durability: concurrent writers merge, corrupt stores quarantine
# ---------------------------------------------------------------------------


def _entry(tag):
    return {"config": PlanConfig(n1=8, n2=8).to_dict(), "mode": "model",
            "modeled_total_s": 1.0, "tag": tag}


def test_concurrent_puts_merge_instead_of_dropping(tmp_path):
    """Two tuners racing on different keys must both land: writer A's
    read-modify-write window is interleaved (via the _race_hook test seam)
    with writer B's complete put — the pre-replace re-read folds B's entry
    into A's payload instead of silently clobbering it."""
    path = str(tmp_path / "plan_cache.json")
    a, b = tune.PlanCache(path), tune.PlanCache(path)
    a._race_hook = lambda: tune.PlanCache.put(b, "key_b", _entry("b"))
    a.put("key_a", _entry("a"))
    entries = tune.PlanCache(path).entries()
    assert set(entries) == {"key_a", "key_b"}
    assert entries["key_a"]["tag"] == "a" and entries["key_b"]["tag"] == "b"


def test_concurrent_same_key_put_is_last_writer_wins(tmp_path):
    path = str(tmp_path / "plan_cache.json")
    a, b = tune.PlanCache(path), tune.PlanCache(path)
    a._race_hook = lambda: tune.PlanCache.put(b, "key", _entry("b"))
    a.put("key", _entry("a"))  # a's replace lands after b's
    assert tune.PlanCache(path).entries()["key"]["tag"] == "a"


def test_corrupt_cache_quarantined_with_one_time_warning(tmp_path):
    """An unparseable store must not be silently treated as empty (which
    re-tuned forever): it is moved aside to .corrupt with one warning, and
    the tuner proceeds on a fresh store."""
    path = str(tmp_path / "plan_cache.json")
    with open(path, "w") as f:
        f.write("{ not json !!")
    cache = tune.PlanCache(path)
    with pytest.warns(RuntimeWarning, match="quarantined"):
        assert cache.entries() == {}
    assert os.path.exists(path + ".corrupt")
    assert not os.path.exists(path)
    # warned once per path per process: a second unreadable store at the
    # same path quarantines again but stays quiet
    with open(path, "w") as f:
        f.write("[1, 2, 3]")  # parseable but not a dict: also corrupt
    import warnings as _w

    with _w.catch_warnings():
        _w.simplefilter("error")
        assert cache.get("anything") is None
    # the store works again after quarantine
    cache.put("k", _entry("fresh"))
    assert cache.get("k")["tag"] == "fresh"


def test_missing_cache_file_is_silently_empty(tmp_path):
    import warnings as _w

    cache = tune.PlanCache(str(tmp_path / "nope.json"))
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert cache.entries() == {}


def test_candidate_configs_sweep_wire_dtypes():
    """The free candidate space sweeps fp32 + bf16 wires (fp16 is opt-in
    via a pin — range-fragile), and a wire_dtype pin collapses the sweep."""
    op = _problem().op
    mesh = make_mesh((1,), ("model",))
    free = tune.candidate_configs(op, mesh)
    assert {c.wire_dtype for c in free} == {"fp32", "bf16"}
    pinned = tune.candidate_configs(op, mesh, pins={"wire_dtype": "fp32"})
    assert {c.wire_dtype for c in pinned} == {"fp32"}
    fp16 = tune.candidate_configs(op, mesh, pins={"wire_dtype": "fp16"})
    assert {c.wire_dtype for c in fp16} == {"fp16"}


def test_group_key_splits_on_wire_dtype():
    """Wire dtype changes the collective payload program, so candidates
    with different wires must never share a lowering/compile group."""
    a = PlanConfig(rfft=True, overlap=1, n1=8, n2=8)
    w = dataclasses.replace(a, wire_dtype="bf16")
    assert tune._group_key(a) != tune._group_key(w)
    assert tune._group_key(w) == tune._group_key(
        dataclasses.replace(w, overlap=4))


def test_one_device_tie_breaks_to_fp32_wire():
    """On a 1-device axis collectives vanish, so every wire models the same
    cost — the tie must break toward the exact fp32 default rather than
    buying bf16 rounding for nothing.  (The real bf16-under-fp32 byte
    ranking needs a multi-device mesh: tests/dist_progs/autotune_prog.py
    and wire_prog.py assert it on compiled 8-device HLO.)"""
    mesh = make_mesh((1,), ("model",))
    cands = [
        PlanConfig(rfft=True, n1=N1, n2=N2, wire_dtype=w)
        for w in ("bf16", "fp32")
    ]
    scored = tune.score_candidates(mesh, cands, batch=1, iters=2)
    assert scored[0][1].wire_dtype == "fp32"
    assert tune.COUNTERS["scored"] == 2  # wire splits the compile group


# ---------------------------------------------------------------------------
# hierarchical candidates + the two-tier cost model
# ---------------------------------------------------------------------------


def test_factored_mesh_auto_enumerates_flat_and_hier():
    """A (host, device) mesh with no pins races the flat layout against the
    hierarchical exchange, with bf16 inter wires only on hier candidates
    (flat has no inter-host hop to demote)."""
    from repro.dist.compat import make_hier_mesh

    op = _problem().op
    mesh = make_hier_mesh(1, 1, 1)
    cands = tune.candidate_configs(op, mesh)
    assert {c.hier_axes for c in cands} == {None, (1, 1)}
    assert all(c.axis_name == ("host", "device") for c in cands)
    assert {c.inter_wire_dtype for c in cands if c.hier_axes is None} \
        == {"fp32"}
    assert {c.inter_wire_dtype for c in cands if c.hier_axes is not None} \
        == {"fp32", "bf16"}
    # a hier pin collapses the sweep; a flat mesh never grows hier candidates
    pinned = tune.candidate_configs(op, mesh, pins={"hier_axes": (1, 1)})
    assert {c.hier_axes for c in pinned} == {(1, 1)}
    flat = tune.candidate_configs(op, make_mesh((1,), ("model",)))
    assert {c.hier_axes for c in flat} == {None}


def test_inter_wire_pin_drops_flat_candidates():
    from repro.dist.compat import make_hier_mesh

    op = _problem().op
    cands = tune.candidate_configs(
        op, make_hier_mesh(1, 1, 1), pins={"inter_wire_dtype": "bf16"}
    )
    assert cands and all(c.hier_axes == (1, 1) for c in cands)
    with pytest.raises(ValueError, match="hierarchical candidate space"):
        tune.candidate_configs(
            op, make_mesh((1,), ("model",)), pins={"inter_wire_dtype": "bf16"}
        )


def test_group_key_splits_on_hier_and_inter_wire():
    """hier compiles different collectives entirely (a2a + permutes vs one
    monolithic a2a) and the inter wire changes the permute payload — neither
    may share a compile with its flat/fp32 twin."""
    a = PlanConfig(rfft=True, overlap=1, n1=8, n2=8,
                   axis_name=("host", "device"))
    h = dataclasses.replace(a, hier_axes=(2, 4))
    hw = dataclasses.replace(h, inter_wire_dtype="bf16")
    assert len({tune._group_key(c) for c in (a, h, hw)}) == 3
    assert tune._group_key(h) == tune._group_key(
        dataclasses.replace(h, overlap=4))


def test_dcn_bytes_policy():
    """Hier plans charge exactly their collective-permute bytes to DCN; a
    flat exchange spanning hosts charges all its all-to-all bytes; single-
    axis plans charge nothing (the bit-for-bit fallback)."""
    from repro.dist.compat import make_hier_mesh

    class _Cost:
        collective_bytes = {"all-to-all": 1000.0, "collective-permute": 250.0}

    mesh_h = make_hier_mesh(1, 1, 1)
    hier = PlanConfig(hier_axes=(1, 1), axis_name=("host", "device"))
    tflat = PlanConfig(axis_name=("host", "device"))
    single = PlanConfig()
    assert tune._dcn_bytes(_Cost(), hier, mesh_h) == 250.0
    # H=1: the "flat" exchange never leaves the host -> ICI only
    assert tune._dcn_bytes(_Cost(), tflat, mesh_h) == 0.0
    assert tune._dcn_bytes(_Cost(), single, make_mesh((1,), ("model",))) == 0.0


def test_two_tier_model_ranks_hier_above_flat():
    """Under the two-tier model a hier block (full payload on ICI + 1/H on
    DCN) must outscore the flat block (full payload on DCN) whenever
    dcn_bw < ici_bw / H — asserted on synthetic costs through the real
    scoring math with the v5e peaks, pinning the win condition the dryrun
    table reports."""
    from repro.launch.roofline import PEAKS, V5E, model_block_times

    pk = PEAKS[V5E]

    class _Cost:
        flops = 1e9
        bytes = 1e6
        collective_bytes: dict = {}

    B, H = 8e8, 2
    flat_cost, hier_cost = _Cost(), _Cost()
    flat_cost.collective_bytes = {"all-to-all": B}
    hier_cost.collective_bytes = {"all-to-all": B,
                                  "collective-permute": B / H}
    assert pk.dcn_bw < pk.ici_bw / H  # the regime the constants encode
    t_flat = model_block_times(flat_cost, dcn_bytes=B, peaks=pk)
    t_hier = model_block_times(hier_cost, dcn_bytes=B / H, peaks=pk)
    assert t_hier["collective_s"] < t_flat["collective_s"]
    assert t_hier["dcn_collective_s"] == pytest.approx(
        t_flat["dcn_collective_s"] / H)
    # and with no DCN bytes the split reproduces the single-tier term
    t0 = model_block_times(flat_cost, peaks=pk)
    assert t0["collective_s"] == B / pk.ici_bw == t0["ici_collective_s"]
    assert t0["dcn_collective_s"] == 0.0


# ---------------------------------------------------------------------------
# peak-rate table keyed by device kind
# ---------------------------------------------------------------------------


def test_peaks_table_v5e_entry():
    from repro.launch.roofline import PEAKS, peaks_for

    pk = peaks_for("TPU v5 lite")
    assert pk is PEAKS["TPU v5 lite"]
    assert (pk.flops, pk.hbm_bw, pk.ici_bw) == (197e12, 819e9, 50e9)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "", "tpu v5 lite"])
def test_peaks_table_unknown_kind_raises(kind, monkeypatch):
    from repro.launch import roofline

    monkeypatch.setattr(roofline, "PEAKS", {"TPU v5 lite": roofline.PEAKS["TPU v5 lite"]})
    with pytest.raises(ValueError, match="no peak rates"):
        roofline.peaks_for(kind)


def test_tuner_refuses_a_device_kind_without_peaks(monkeypatch):
    """Scoring on a mesh whose device kind has no peak entry is an error,
    not a silent default."""
    from repro.launch import roofline

    monkeypatch.delitem(roofline.PEAKS, jax.devices()[0].device_kind)
    mesh = make_mesh((1,), ("model",))
    cands = tune.candidate_configs(_problem().op, mesh, pins={"rfft": False})
    with pytest.raises(ValueError, match="no peak rates"):
        tune.score_candidates(mesh, cands[:1], batch=1, iters=2)

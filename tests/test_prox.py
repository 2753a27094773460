"""The pluggable prox layer (repro.ops.prox): properties, pins, threading.

Three layers of contract, each pinned:

  * operator properties — every prox is (firmly) non-expansive, batched
    application equals the per-signal loop, TV/wavelet have the right fixed
    points and adjoints;
  * bit-exactness — ``L1Prox`` is the paper's soft threshold *bitwise*, and
    threading ``prox=None`` / ``prox=L1Prox()`` through every solver,
    compressor and plan entry point reproduces the pre-refactor iterates
    bit-for-bit (the fused Pallas tails stay eligible);
  * plan/serve integration — ``PlanConfig`` validates/serializes/describes
    the prox, planned solves match core ones per prior, and serve buckets
    keyed by distinct ``prox=`` tags never share an engine.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RecoveryProblem, partial_gaussian_circulant, solve, soft_threshold
from repro.core.compression import decode, make_compressor
from repro.core.solvers import make_stepper
from repro.data.synthetic import paper_regime, sparse_signal
from repro.ops import PlanConfig, plan
from repro.ops.prox import (
    PROX_KINDS,
    L1Prox,
    NonNegL1Prox,
    TVProx,
    WaveletProx,
    is_elementwise,
    is_l1,
    prox_from_dict,
    prox_to_dict,
)

SOLVE_KW = dict(alpha=1e-3, rho=0.01, sigma=0.01)
METHODS = ("ista", "fista", "cpadmm")

ALL_PROXES = [
    L1Prox(),
    NonNegL1Prox(),
    TVProx(shape=(8, 8)),
    WaveletProx(levels=2, wavelet="haar"),
    WaveletProx(levels=1, wavelet="db4"),
]


def _ids(proxes):
    return [p.tag for p in proxes]


def _rel(got, want):
    got, want = jnp.asarray(got), jnp.asarray(want)
    return float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30))


def _problem(n=256, batch=2, seed=0):
    m, k = paper_regime(n)
    x_true = sparse_signal(jax.random.PRNGKey(seed), n, k, batch=(batch,))
    op = partial_gaussian_circulant(jax.random.PRNGKey(seed + 1), n, m,
                                    normalize=True)
    return RecoveryProblem(op=op, y=op.matvec(x_true), x_true=x_true)


# -- operator properties ----------------------------------------------------


@pytest.mark.parametrize("prox", ALL_PROXES, ids=_ids(ALL_PROXES))
def test_prox_nonexpansive(prox):
    """||prox(x) - prox(y)|| <= ||x - y|| — definitional for a prox of a
    convex function; a broken inner loop (TV) or non-orthonormal filter bank
    (wavelet) violates it."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    for gamma in (0.01, 0.3):
        x = jax.random.normal(k1, (64,))
        y = jax.random.normal(k2, (64,))
        lhs = float(jnp.linalg.norm(prox.apply(x, gamma) - prox.apply(y, gamma)))
        rhs = float(jnp.linalg.norm(x - y))
        assert lhs <= rhs * (1 + 1e-5), (prox.tag, gamma)


@pytest.mark.parametrize("prox", ALL_PROXES, ids=_ids(ALL_PROXES))
def test_prox_batched_equals_loop(prox):
    """Batch axes broadcast: prox of a (B, n) stack == stacking per-signal
    applications (the solver batching contract)."""
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 64))
    got = prox.apply(x, 0.1)
    want = jnp.stack([prox.apply(x[i], 0.1) for i in range(3)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_l1_prox_is_soft_threshold_bitwise():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 128)) * 2.0
    for gamma in (0.0, 0.05, 1.5):
        got = L1Prox().apply(x, gamma)
        want = soft_threshold(x, gamma)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_nonneg_l1_prox():
    x = jnp.array([-1.0, -0.05, 0.05, 1.0])
    got = np.asarray(NonNegL1Prox().apply(x, 0.1))
    np.testing.assert_allclose(got, [0.0, 0.0, 0.0, 0.9], atol=1e-7)
    assert (got >= 0).all()


def test_tv_prox_constant_fixed_point():
    """A constant image has zero TV: the prox must return it unchanged."""
    x = jnp.full((64,), 0.7)
    got = TVProx(shape=(8, 8)).apply(x, 0.5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x), atol=1e-6)


def test_tv_prox_reduces_tv_norm():
    prox = TVProx(shape=(8, 8), iters=20)
    x = jax.random.normal(jax.random.PRNGKey(1), (64,))

    def tv(v):
        img = v.reshape(8, 8)
        return float(
            jnp.abs(jnp.roll(img, -1, 0) - img).sum()
            + jnp.abs(jnp.roll(img, -1, 1) - img).sum()
        )

    z = prox.apply(x, 0.2)
    assert tv(z) < tv(x)


def test_tv_analysis_adjoint():
    """<D x, p> == <x, D^T p> — the dual inner loop silently diverges if
    the roll-based adjoint pair drifts."""
    prox = TVProx(shape=(8, 8))
    kx, kp = jax.random.split(jax.random.PRNGKey(2))
    x = jax.random.normal(kx, (64,))
    p = jax.random.normal(kp, (128,))
    lhs = float(jnp.vdot(prox.analysis_op(x), p))
    rhs = float(jnp.vdot(x, prox.analysis_rmatvec(p)))
    assert lhs == pytest.approx(rhs, rel=1e-5)


@pytest.mark.parametrize("wavelet", ["haar", "db4"])
def test_wavelet_prox_perfect_reconstruction(wavelet):
    """gamma=0 thresholds nothing: W^T W x == x (orthonormal filter bank)."""
    prox = WaveletProx(levels=2, wavelet=wavelet)
    x = jax.random.normal(jax.random.PRNGKey(4), (64,))
    np.testing.assert_allclose(
        np.asarray(prox.apply(x, 0.0)), np.asarray(x), atol=2e-6
    )
    # analysis is orthonormal: energy preserved
    c = prox.analysis_op(x)
    assert float(jnp.vdot(c, c)) == pytest.approx(float(jnp.vdot(x, x)), rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(prox.analysis_rmatvec(c)), np.asarray(x), atol=2e-6
    )


def test_prox_validation_errors():
    with pytest.raises(ValueError, match="shape"):
        TVProx(shape=(0, 8))
    with pytest.raises(ValueError, match="iters"):
        TVProx(shape=(8, 8), iters=0)
    with pytest.raises(ValueError, match="wavelet"):
        WaveletProx(wavelet="sym9")
    with pytest.raises(ValueError, match="levels"):
        WaveletProx(levels=0)
    # trailing-dim mismatch is loud, not a silent reshape
    with pytest.raises(ValueError):
        TVProx(shape=(8, 8)).apply(jnp.zeros(63), 0.1)
    with pytest.raises(ValueError):
        WaveletProx(levels=3).apply(jnp.zeros(12), 0.1)


# -- registry + serialization ----------------------------------------------


def test_prox_serialization_round_trip():
    for prox in ALL_PROXES:
        d = prox_to_dict(prox)
        json.dumps(d)  # JSON-safe (the tune cache stores pins this way)
        back = prox_from_dict(d)
        assert back == prox and type(back) is type(prox)
    assert prox_to_dict(None) is None and prox_from_dict(None) is None
    assert set(PROX_KINDS) == {"l1", "nonneg-l1", "tv", "wavelet"}
    with pytest.raises(ValueError, match="kind"):
        prox_from_dict({"kind": "nope"})


def test_prox_helpers_and_hashability():
    assert is_l1(None) and is_l1(L1Prox())
    assert not is_l1(TVProx(shape=(4, 4))) and not is_l1(NonNegL1Prox())
    assert is_elementwise(None) and is_elementwise(NonNegL1Prox())
    assert not is_elementwise(TVProx(shape=(4, 4)))
    assert not is_elementwise(WaveletProx())
    # frozen dataclasses: usable as jit static args / dict keys
    assert len({L1Prox(), L1Prox(), TVProx(shape=(4, 4))}) == 2


# -- solver threading: bit-exactness + composability ------------------------


@pytest.mark.parametrize("method", METHODS)
def test_solver_none_vs_l1prox_bitwise(method):
    """The refactor's central pin: prox=None (pre-refactor expressions,
    verbatim) and prox=L1Prox() produce bit-identical iterates."""
    prob = _problem()
    x0, _ = solve(prob, method, iters=40, record_every=40, plan=plan(prob.op),
                  **SOLVE_KW)
    x1, _ = solve(prob, method, iters=40, record_every=40,
                  plan=plan(prob.op, prox=L1Prox()), **SOLVE_KW)
    np.testing.assert_array_equal(np.asarray(x0), np.asarray(x1))


def test_cpadmm_pallas_tail_l1_only():
    """tail='pallas' stays on the fused kernel for the l1 prior (bit-exact
    vs the jnp tail in interpret mode) and refuses a non-l1 prox — the
    fused kernel bakes in the soft threshold, so the plan would otherwise
    have to run the jnp tail in place of the kernel it asked for."""
    prob = _problem(batch=1)
    prob = RecoveryProblem(op=prob.op, y=prob.y[0], x_true=prob.x_true[0])
    pl_jnp = plan(prob.op, tail="jnp")
    pl_pal = plan(prob.op, tail="pallas")
    x_j, _ = solve(prob, "cpadmm", iters=20, record_every=20, plan=pl_jnp,
                   **SOLVE_KW)
    x_p, _ = solve(prob, "cpadmm", iters=20, record_every=20, plan=pl_pal,
                   **SOLVE_KW)
    assert _rel(x_p, x_j) < 1e-6
    # a non-l1 prox through the pallas-tagged plan is an error, not a
    # silent jnp fallback — at solve time and in the plan config
    prox = NonNegL1Prox()
    with pytest.raises(ValueError, match="tail='pallas'"):
        solve(prob, "cpadmm", iters=20, record_every=20, plan=pl_pal,
              prox=prox, **SOLVE_KW)
    with pytest.raises(ValueError, match="tail='pallas'"):
        plan(prob.op, tail="pallas", prox=prox)
    x_r, _ = solve(prob, "cpadmm", iters=20, record_every=20, plan=pl_jnp,
                   prox=prox, **SOLVE_KW)
    assert float(x_r.min()) >= 0.0


@pytest.mark.parametrize(
    "prox",
    [NonNegL1Prox(), TVProx(shape=(16, 16)), WaveletProx()],
    ids=["nonneg-l1", "tv", "wavelet"],
)
@pytest.mark.parametrize("method", METHODS)
def test_solver_non_l1_proxes_run(method, prox):
    prob = _problem()
    x, _ = solve(prob, method, iters=40, record_every=40,
                 plan=plan(prob.op, prox=prox), **SOLVE_KW)
    assert x.shape == prob.x_true.shape
    assert bool(jnp.all(jnp.isfinite(x)))
    # the prior actually engaged: result differs from the l1 solve
    x_l1, _ = solve(prob, method, iters=40, record_every=40,
                    plan=plan(prob.op), **SOLVE_KW)
    assert not jnp.array_equal(x, x_l1)


def test_make_stepper_prox_defaults_to_plan():
    """make_stepper(prob, m, plan=pl) picks up pl.prox; an explicit prox=
    argument overrides it."""
    prob = _problem()
    pl = plan(prob.op, prox=NonNegL1Prox())
    st = make_stepper(prob, "cpadmm", plan=pl, **SOLVE_KW)
    s = st.init()
    for _ in range(10):
        s = st.step(s)
    assert float(st.extract(s).min()) >= 0.0  # nonneg prox engaged
    st2 = make_stepper(prob, "cpadmm", plan=pl, prox=L1Prox(), **SOLVE_KW)
    st3 = make_stepper(prob, "cpadmm", plan=plan(prob.op), **SOLVE_KW)
    s2, s3 = st2.init(), st3.init()
    for _ in range(10):
        s2, s3 = st2.step(s2), st3.step(s3)
    np.testing.assert_array_equal(
        np.asarray(st2.extract(s2)), np.asarray(st3.extract(s3))
    )


# -- compression satellite --------------------------------------------------


def test_compression_decode_l1_bitwise():
    """The compressor's decode routes through the prox layer; the default
    spec (prox=None) must be bit-identical to an explicit L1Prox spec."""
    spec0, state = make_compressor(jax.random.PRNGKey(0), 200, ratio=4)
    spec1, _ = make_compressor(jax.random.PRNGKey(0), 200, ratio=4,
                               prox=L1Prox())
    assert spec0.prox is None and isinstance(spec1.prox, L1Prox)
    g = sparse_signal(jax.random.PRNGKey(2), spec0.n, 12)
    y = jnp.take(
        jnp.fft.irfft(
            jnp.fft.rfft(state.col) * jnp.fft.rfft(g), n=spec0.n
        ).astype(jnp.float32),
        state.omega,
    )
    np.testing.assert_array_equal(
        np.asarray(decode(spec0, state, y)), np.asarray(decode(spec1, state, y))
    )


def test_compression_decode_nonneg_prox():
    spec, state = make_compressor(jax.random.PRNGKey(1), 200, ratio=4,
                                  prox=NonNegL1Prox())
    y = jax.random.normal(jax.random.PRNGKey(3), (spec.m,))
    x = decode(spec, state, y)
    assert float(x.min()) >= 0.0


# -- plan layer: config, parity, serve buckets ------------------------------


def test_plan_config_prox_validation_and_describe():
    cfg = PlanConfig(prox=TVProx(shape=(8, 8), iters=5))
    cfg.validate(distributed=False)
    assert "prox=tv[8x8,it5]" in cfg.describe()
    assert "prox=" not in PlanConfig().describe()  # default stays tagless
    with pytest.raises(ValueError, match="prox"):
        PlanConfig(prox="tv").validate(distributed=False)
    back = PlanConfig.from_dict(cfg.to_dict())
    assert back.prox == cfg.prox
    json.dumps(cfg.to_dict())


@pytest.mark.parametrize(
    "prox",
    [None, L1Prox(), NonNegL1Prox(), TVProx(shape=(16, 16)), WaveletProx()],
    ids=["none", "l1", "nonneg-l1", "tv", "wavelet"],
)
@pytest.mark.parametrize("method", ("ista", "cpadmm"))
def test_planned_mesh_matches_local_per_prior(method, prox):
    """Distributed (1-device mesh: same collectives code, cheap in CI) ==
    local at 1e-5 rel for every prior; the 8-device variant rides
    tests/dist_progs/prox_prog.py."""
    from repro.dist.compat import make_mesh

    prob = _problem()
    pl_l = plan(prob.op, prox=prox)
    pl_d = plan(prob.op, make_mesh((1,), ("model",)), prox=prox)
    x_l, _ = solve(prob, method, iters=30, record_every=30, plan=pl_l,
                   **SOLVE_KW)
    x_d, _ = solve(prob, method, iters=30, record_every=30, plan=pl_d,
                   **SOLVE_KW)
    assert _rel(x_d, x_l) <= 1e-5, (method, prox and prox.tag)


def test_planned_mesh_none_vs_l1_bitwise():
    """On the mesh path too, None and L1Prox() share the fused lowering."""
    from repro.dist.compat import make_mesh

    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    for method in ("ista", "cpadmm"):
        x0, _ = solve(prob, method, iters=30, record_every=30,
                      plan=plan(prob.op, mesh), **SOLVE_KW)
        x1, _ = solve(prob, method, iters=30, record_every=30,
                      plan=plan(prob.op, mesh, prox=L1Prox()), **SOLVE_KW)
        np.testing.assert_array_equal(np.asarray(x0), np.asarray(x1))


def test_tuner_candidates_carry_prox_pin():
    from repro.dist.compat import make_mesh
    from repro.ops.tune import cache_key, candidate_configs

    mesh = make_mesh((1,), ("model",))
    op = _problem().op
    prox = TVProx(shape=(16, 16))
    cands = candidate_configs(op, mesh, pins={"prox": prox})
    assert cands and all(c.prox == prox for c in cands)
    # distinct prox pins key distinct cache entries
    k_tv = cache_key(op, mesh, 2, {"prox": prox})
    k_l1 = cache_key(op, mesh, 2, {"prox": L1Prox()})
    k_none = cache_key(op, mesh, 2, {})
    assert len({k_tv, k_l1, k_none}) == 3


def test_serve_buckets_split_on_prox():
    """Requests differing only in the plan config's prox never share an
    engine (ISSUE acceptance: distinct prox= tags, distinct buckets)."""
    from repro.serve import RecoveryRequest, RecoveryServer

    op = _problem().op
    y = jnp.zeros((op.m,), jnp.float32)
    server = RecoveryServer(slots=2)

    def req(rid, cfg):
        return RecoveryRequest(request_id=rid, op=op, y=y, plan_config=cfg)

    k_l1 = server.bucket_key(req("a", PlanConfig()))
    k_tv = server.bucket_key(req("b", PlanConfig(prox=TVProx(shape=(16, 16)))))
    k_wv = server.bucket_key(req("c", PlanConfig(prox=WaveletProx())))
    assert len({k_l1, k_tv, k_wv}) == 3


@pytest.mark.parametrize(
    "prox",
    [NonNegL1Prox(), TVProx(shape=(16, 16)), WaveletProx(levels=2, wavelet="haar")],
    ids=lambda p: p.tag,
)
def test_plan_config_rejects_pallas_tail_with_non_l1_prior(prox):
    """PlanConfig.validate() is where tail='pallas' + non-l1 prior dies —
    local and distributed alike; the l1 priors stay eligible."""
    with pytest.raises(ValueError, match="tail='pallas'"):
        PlanConfig(tail="pallas", prox=prox).validate(distributed=False)
    with pytest.raises(ValueError, match="tail='pallas'"):
        PlanConfig(tail="pallas", prox=prox).validate(distributed=True)
    PlanConfig(tail="jnp", prox=prox).validate(distributed=False)
    PlanConfig(tail="pallas", prox=L1Prox()).validate(distributed=False)


def test_tuner_never_offers_pallas_tail_for_non_l1(monkeypatch):
    """On the chip the tuner races jnp vs pallas tails — but only for the
    l1 prior the fused kernel implements."""
    from repro.dist.compat import make_mesh
    from repro.ops import tune

    monkeypatch.setattr(tune.jax, "default_backend", lambda: "tpu")
    mesh = make_mesh((1,), ("model",))
    op = _problem().op
    l1 = tune.candidate_configs(op, mesh, pins={})
    assert {c.tail for c in l1} == {"jnp", "pallas"}
    nn = tune.candidate_configs(op, mesh, pins={"prox": NonNegL1Prox()})
    assert {c.tail for c in nn} == {"jnp"}

"""Execution-plan layer (repro.ops): one driver stack, every backend.

Pins the ISSUE 4 contract:
  * ``plan(op)`` with no mesh is the identity lowering — every core matvec
    reproduced bit-exactly, and the drivers unchanged.
  * ``plan(op, mesh)`` lowers ista / fista / cpadmm onto the sharded
    four-step transforms; ``solve`` / ``solve_until`` / ``solve_checkpointed``
    match the single-device solver to 1e-5 relative error (the in-process
    1-device-mesh variant of tests/dist_progs/ista_prog.py).
  * ``make_dist_cpadmm`` survives as a deprecation shim with identical
    output to the plan route.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RecoveryProblem, densify, solve, solve_checkpointed, solve_until
from repro.core.circulant import PartialCirculant, gaussian_circulant
from repro.data.synthetic import paper_regime, sparse_signal
from repro.dist.compat import make_mesh
from repro.dist.fft import layout_2d, unlayout_2d
from repro.dist.recovery import make_dist_cpadmm
from repro.ops import ExecutionPlan, PlanConfig, RecoveryOperator, plan, plan_from_parts

N1, N2 = 32, 16
N = N1 * N2
ALPHA, RHO, SIGMA = 1e-4, 0.01, 0.01


def _problem(batch=()):
    # drawn under JAX's original threefry stream, which the iteration
    # budgets below were sized on (JAX 0.9 made the partitionable stream
    # the default; its redraw of this instance leaves FISTA short of
    # convergence at 800 iterations)
    with jax.threefry_partitionable(False):
        x_true = sparse_signal(jax.random.PRNGKey(0), N, paper_regime(N)[1],
                               batch=batch)
        C = gaussian_circulant(jax.random.PRNGKey(1), N, normalize=True)
        m = paper_regime(N)[0]
        omega = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), N)[:m])
    op = PartialCirculant(C, omega.astype(jnp.int32))
    return RecoveryProblem(op=op, y=op.matvec(x_true), x_true=x_true)


def _rel(got, want):
    got, want = jnp.asarray(got), jnp.asarray(want)
    return float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30))


# ---------------------------------------------------------------------------
# local plans: the identity lowering, bit-exact
# ---------------------------------------------------------------------------


def test_local_plan_reproduces_every_core_matvec_bit_exactly():
    prob = _problem()
    x = jax.random.normal(jax.random.PRNGKey(3), (N,))
    ops = [prob.op, prob.op.circ, densify(prob.op)]
    for op in ops:
        assert isinstance(op, RecoveryOperator)
        pl = plan(op)
        assert isinstance(pl, ExecutionPlan) and not pl.is_distributed
        assert pl.operator is op  # the identity lowering, by construction
        np.testing.assert_array_equal(
            np.asarray(pl.matvec(x)), np.asarray(op.matvec(x))
        )
        y = op.matvec(x)
        np.testing.assert_array_equal(
            np.asarray(pl.rmatvec(y)), np.asarray(op.rmatvec(y))
        )


def test_local_plan_drivers_bit_exact():
    """solve(plan=local_plan) is the same computation as solve()."""
    prob = _problem()
    pl = plan(prob.op)
    for method in ("ista", "fista", "cpadmm"):
        x0, _ = solve(prob, method, iters=40, record_every=40,
                      alpha=ALPHA, rho=RHO, sigma=SIGMA)
        x1, _ = solve(prob, method, iters=40, record_every=40,
                      alpha=ALPHA, rho=RHO, sigma=SIGMA, plan=pl)
        np.testing.assert_array_equal(np.asarray(x0), np.asarray(x1))


def test_local_plan_pallas_tail_matches_jnp():
    """tail='pallas' on the local backend: the fused cpadmm_tail kernel
    (interpret mode on CPU) reproduces the jnp stepper."""
    prob = _problem()
    iters = 25  # interpret-mode Pallas per iteration: keep the scan short
    x_jnp, _ = solve(prob, "cpadmm", iters=iters, record_every=iters,
                     alpha=ALPHA, rho=RHO, sigma=SIGMA)
    x_pal, _ = solve(prob, "cpadmm", iters=iters, record_every=iters,
                     alpha=ALPHA, rho=RHO, sigma=SIGMA,
                     plan=plan(prob.op, tail="pallas"))
    assert _rel(x_pal, x_jnp) <= 1e-5


# ---------------------------------------------------------------------------
# distributed plans on a 1-device mesh (fast lane; 8 devices in dist_progs/)
# ---------------------------------------------------------------------------

# (method, iters) — fista runs to convergence: its momentum transiently
# amplifies the four-step-FFT rounding noise mid-trajectory, and the 1e-5
# contract is about the *recovered signal*, not a mid-flight iterate.
DIST_CASES = [("ista", 300), ("fista", 800), ("cpadmm", 300)]


@pytest.mark.parametrize("method,iters", DIST_CASES)
@pytest.mark.parametrize("rfft", [False, True])
def test_dist_plan_solve_matches_core(method, iters, rfft):
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    pl = plan(prob.op, mesh, n1=N1, n2=N2, rfft=rfft)
    x_ref, _ = solve(prob, method, iters=iters, record_every=iters,
                     alpha=ALPHA, rho=RHO, sigma=SIGMA)
    x_dist, tr = solve(prob, method, iters=iters, record_every=iters,
                       alpha=ALPHA, rho=RHO, sigma=SIGMA, plan=pl)
    rel = _rel(x_dist, x_ref)
    assert rel <= 1e-5, f"{method} rfft={rfft}: {rel:.2e}"
    # distributed runs now get the core drivers' metric traces
    assert jnp.isfinite(tr.objective).all() and jnp.isfinite(tr.mse).all()


@pytest.mark.parametrize("method", ["ista", "cpadmm"])
def test_dist_plan_solve_until_matches_core(method):
    """Tolerance-stopped *distributed* recovery — previously impossible."""
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    pl = plan(prob.op, mesh, n1=N1, n2=N2, rfft=True)
    kw = dict(tol=1e-7, max_iters=3000, alpha=ALPHA, rho=RHO, sigma=SIGMA)
    x_ref, used_ref = solve_until(prob, method, **kw)
    x_dist, used = solve_until(prob, method, plan=pl, **kw)
    assert _rel(x_dist, x_ref) <= 1e-5
    assert int(used) > 0 and int(used_ref) > 0


@pytest.mark.parametrize("method", ["ista", "cpadmm"])
def test_dist_plan_solve_checkpointed_restarts(method):
    """Checkpoint/restart of a distributed solve: resuming from the first
    saved state reproduces the uninterrupted run exactly, and both match
    the single-device result."""
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    pl = plan(prob.op, mesh, n1=N1, n2=N2, rfft=True)
    kw = dict(iters=300, chunk=100, alpha=ALPHA, rho=RHO, sigma=SIGMA)
    saves = []
    x_full, _ = solve_checkpointed(
        prob, method, plan=pl, save_cb=lambda s, st: saves.append((s, st)), **kw
    )
    assert [s for s, _ in saves] == [100, 200, 300]
    # sharded-layout state leaves: (n1, n2), not flat (momentum scalars aside)
    assert all(
        leaf.shape[-2:] == (N1, N2)
        for leaf in jax.tree.leaves(saves[0][1])
        if leaf.ndim >= 2
    )
    x_resumed, _ = solve_checkpointed(prob, method, plan=pl, restore=saves[0], **kw)
    np.testing.assert_array_equal(np.asarray(x_full), np.asarray(x_resumed))
    x_ref, _ = solve_checkpointed(prob, method, **kw)
    assert _rel(x_full, x_ref) <= 1e-5


def test_dist_plan_batched_matches_core():
    """A leading batch rides the dist plan (replicated batch on a model-only
    mesh) with per-signal results matching the batched core solver."""
    B = 3
    prob = _problem(batch=(B,))
    mesh = make_mesh((1,), ("model",))
    pl = plan(prob.op, mesh, n1=N1, n2=N2, rfft=True)
    x_ref, _ = solve(prob, "cpadmm", iters=300, record_every=300,
                     alpha=ALPHA, rho=RHO, sigma=SIGMA)
    x_dist, _ = solve(prob, "cpadmm", iters=300, record_every=300,
                      alpha=ALPHA, rho=RHO, sigma=SIGMA, plan=pl)
    assert x_dist.shape == (B, N)
    for b in range(B):
        assert _rel(x_dist[b], x_ref[b]) <= 1e-5


def test_dist_plan_mask_form_operator():
    """The planned operator is diag(mask) C on flat arrays: same normal
    equations as the m-subset form (the solver-equivalence workhorse)."""
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    pl = plan(prob.op, mesh, n1=N1, n2=N2)
    x = jax.random.normal(jax.random.PRNGKey(4), (N,))
    mask = jnp.zeros((N,)).at[prob.op.omega].set(1.0)
    want_mv = mask * prob.op.circ.matvec(x)
    got_mv = pl.operator.matvec(x)
    scale = float(jnp.max(jnp.abs(want_mv)))
    np.testing.assert_allclose(
        np.asarray(got_mv), np.asarray(want_mv), atol=1e-5 * scale
    )
    # A^T y on scattered measurements == rmatvec of the m-subset operator
    y_full = mask * prob.op.circ.matvec(x)
    want_rmv = prob.op.rmatvec(jnp.take(y_full, prob.op.omega))
    got_rmv = pl.operator.rmatvec(y_full)
    scale = float(jnp.max(jnp.abs(want_rmv)))
    np.testing.assert_allclose(
        np.asarray(got_rmv), np.asarray(want_rmv), atol=1e-5 * scale
    )
    np.testing.assert_allclose(
        float(pl.operator.operator_norm_bound()),
        float(prob.op.operator_norm_bound()),
        rtol=1e-6,
    )


@pytest.mark.parametrize("shape", [(32, 16), (31, 33)])
@pytest.mark.parametrize("rfft", [False, True])
def test_spectrum_layout_matches_distributed_fft(shape, rfft):
    """plan()'s direct spectrum re-layout (spectral.spectrum_layout_2d — no
    time-domain round trip) produces the same column block the four-step
    transform of the first column does, on even and odd extents."""
    from repro.dist.recovery import make_dist_spectrum
    from repro.ops import spectral

    n1, n2 = shape
    col = jax.random.normal(jax.random.PRNGKey(5), (n1 * n2,))
    mesh = make_mesh((1,), ("model",))
    want = make_dist_spectrum(mesh, rfft=rfft)(layout_2d(col, n1, n2))
    got = spectral.spectrum_layout_2d(
        jnp.fft.rfft(col), n1, n2, rfft=rfft, p=1
    )
    assert got.shape == want.shape
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5 * scale
    )


# ---------------------------------------------------------------------------
# deprecation shim
# ---------------------------------------------------------------------------


def test_make_dist_cpadmm_shim_warns_and_matches_plan_route():
    prob = _problem()
    C, omega = prob.op.circ, prob.op.omega
    mask = jnp.zeros((N,)).at[omega].set(1.0)
    mesh = make_mesh((1,), ("model",))
    iters = 150

    with pytest.warns(DeprecationWarning, match="make_dist_cpadmm is deprecated"):
        solver = make_dist_cpadmm(mesh, N1, N2, iters, fused=True, rfft=True)
    pl = plan(prob.op, mesh, n1=N1, n2=N2, rfft=True)
    z_shim = solver(
        pl.spec2d,
        layout_2d(mask, N1, N2),
        layout_2d(mask * C.matvec(prob.x_true), N1, N2),
        jnp.float32(ALPHA), jnp.float32(RHO), jnp.float32(SIGMA),
    )
    z_plan, _ = solve(prob, "cpadmm", iters=iters, record_every=iters,
                      alpha=ALPHA, rho=RHO, sigma=SIGMA, plan=pl)
    # identical computation; the shim's single outer jit fuses differently
    # than the eager chunked route, so "identical" means float32-roundoff
    # (an order tighter than the 1e-5 solver acceptance gate)
    assert _rel(unlayout_2d(z_shim), z_plan) <= 1e-6


def test_shim_rejects_unknown_batch_axis():
    mesh = make_mesh((1,), ("model",))
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="batch_axis"):
            make_dist_cpadmm(mesh, N1, N2, 10, batch_axis="data")


# ---------------------------------------------------------------------------
# validation / error surfaces
# ---------------------------------------------------------------------------


def test_unknown_method_error_lists_valid_methods():
    prob = _problem()
    with pytest.raises(ValueError, match="ista, fista, cpista, admm, padmm, cpadmm"):
        solve(prob, "newton")


def test_dist_plan_method_without_lowering_errors():
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    pl = plan(prob.op, mesh, n1=N1, n2=N2)
    with pytest.raises(ValueError, match="no distributed lowering"):
        solve(prob, "admm", plan=pl)


def test_plan_validation_errors():
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="n1 \\* n2"):
        plan(prob.op, mesh, n1=7, n2=11)
    with pytest.raises(TypeError, match="circulant"):
        plan(densify(prob.op), mesh)
    with pytest.raises(ValueError, match="tail"):
        plan(prob.op, tail="cuda")


def test_plan_auto_factorization():
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    pl = plan(prob.op, mesh)  # N = 512 -> 16 x 32
    assert pl.n1 * pl.n2 == N and pl.n1 <= pl.n2
    x_ref, _ = solve(prob, "ista", iters=100, record_every=100, alpha=ALPHA)
    x_dist, _ = solve(prob, "ista", iters=100, record_every=100, alpha=ALPHA,
                      plan=pl)
    assert _rel(x_dist, x_ref) <= 1e-5


# ---------------------------------------------------------------------------
# PlanConfig API (ISSUE 6): one config object, four entry points, one
# validation site
# ---------------------------------------------------------------------------


def test_plan_config_is_frozen_and_hashable():
    cfg = PlanConfig(rfft=True, overlap=2, n1=N1, n2=N2)
    assert hash(cfg) == hash(PlanConfig(rfft=True, overlap=2, n1=N1, n2=N2))
    with pytest.raises(Exception):  # dataclasses.FrozenInstanceError
        cfg.rfft = False
    assert "rfft=on" in cfg.describe() and "overlap=2" in cfg.describe()


def test_plan_accepts_config_with_legacy_parity():
    """config=PlanConfig(...) builds the identical plan the legacy kwargs
    spell, at every entry point that takes knobs."""
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    cfg = PlanConfig(rfft=True, overlap=2, n1=N1, n2=N2)
    via_cfg = plan(prob.op, mesh, config=cfg)
    via_kw = plan(prob.op, mesh, rfft=True, overlap=2, n1=N1, n2=N2)
    assert via_cfg.config == via_kw.config == cfg
    x = jax.random.normal(jax.random.PRNGKey(6), (N,))
    np.testing.assert_array_equal(
        np.asarray(via_cfg.matvec(x)), np.asarray(via_kw.matvec(x))
    )


def test_plan_from_parts_accepts_config_with_legacy_parity():
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    donor = plan(prob.op, mesh, n1=N1, n2=N2)
    mask2d = layout_2d(jnp.zeros((N,)).at[prob.op.omega].set(1.0), N1, N2)
    cfg = PlanConfig(n1=N1, n2=N2)
    via_cfg = plan_from_parts(mesh, donor.spec2d, mask2d, config=cfg)
    via_kw = plan_from_parts(mesh, donor.spec2d, mask2d, n1=N1, n2=N2)
    assert via_cfg.config == via_kw.config == cfg


def test_build_plan_accepts_config_with_legacy_parity():
    from repro.launch import recover

    prob = _problem()
    cfg = PlanConfig(rfft=True, n1=N1, n2=N2)
    via_cfg = recover.build_plan(prob.op, "1", config=cfg)
    via_kw = recover.build_plan(prob.op, "1", n1=N1, rfft=True)
    assert via_cfg.config == via_kw.config == cfg


def test_build_deblur_plan_accepts_config_with_legacy_parity():
    from repro.core.deblur import build_deblur_plan, build_deblur_problem
    from repro.data.synthetic import starfield

    img = starfield(jax.random.PRNGKey(7), 16, 16, density=0.05, n_blobs=2)
    dp = build_deblur_problem(jax.random.PRNGKey(8), img, blur_order=3,
                              subsample=0.5, sensing="romberg")
    mesh = make_mesh((1,), ("model",))
    cfg = PlanConfig(rfft=True, n1=16, n2=16)
    via_cfg = build_deblur_plan(dp, mesh, config=cfg)
    via_kw = build_deblur_plan(dp, mesh, rfft=True, n1=16, n2=16)
    assert via_cfg.config == via_kw.config == cfg


def test_config_plus_legacy_knobs_is_an_error():
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    cfg = PlanConfig(n1=N1, n2=N2)
    with pytest.raises(ValueError, match=r"not both.*rfft"):
        plan(prob.op, mesh, config=cfg, rfft=True)
    with pytest.raises(ValueError, match="not both"):
        plan_from_parts(mesh, None, None, config=cfg, overlap=2)


def test_local_plan_rejects_distributed_knobs():
    """The single validation site: rfft/overlap/batch_axis without a mesh
    used to be silently ignored — now they refuse loudly."""
    prob = _problem()
    for bad in (dict(rfft=True), dict(overlap=4), dict(batch_axis="data")):
        with pytest.raises(ValueError, match="pass a mesh"):
            plan(prob.op, **bad)


def test_plan_from_parts_requires_concrete_factorization():
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="no operator to infer n"):
        plan_from_parts(mesh, None, None, config=PlanConfig(rfft=True))


def test_plan_config_validate_messages():
    with pytest.raises(ValueError, match="tail must be"):
        PlanConfig(tail="cuda").validate(distributed=False)
    with pytest.raises(ValueError, match="overlap"):
        PlanConfig(overlap=0).validate(distributed=True)
    with pytest.raises(ValueError, match="positive"):
        PlanConfig(n1=-4, n2=8).validate(distributed=True)


# ---------------------------------------------------------------------------
# make_dist_cpadmm deprecation endgame
# ---------------------------------------------------------------------------


def test_shim_warning_pins_removal_version():
    mesh = make_mesh((1,), ("model",))
    with pytest.warns(
        DeprecationWarning,
        match=r"make_dist_cpadmm is deprecated and will be removed in "
              r"repro 0\.2\.0",
    ):
        make_dist_cpadmm(mesh, N1, N2, 1)


def test_make_dist_cpadmm_not_exported_from_dist_package():
    import repro.dist as dist

    assert "make_dist_cpadmm" not in dist.__all__
    assert "make_dist_cpadmm" not in dir(dist)
    with pytest.raises(AttributeError, match="make_dist_cpadmm"):
        dist.make_dist_cpadmm
    # the lazy symbol table still serves everything that IS public
    assert dist.MODEL_AXIS == "model"
    assert dist.make_mesh is make_mesh
    assert callable(dist.dist_cpadmm_step)
    assert set(dist.__all__) >= {"layout_2d", "make_distributed_rfft",
                                 "rules_for_arch", "DistCpadmmParams"}


# ---------------------------------------------------------------------------
# wire-compressed collectives (ISSUE 8): wire_dtype on the plan layer
# ---------------------------------------------------------------------------


def test_local_plan_rejects_wire_dtype_loudly():
    """The single validation site refuses a demoted wire without a mesh —
    a local plan has no all-to-all to compress, and silently ignoring the
    knob would hide the 2x byte win the caller thinks they asked for."""
    prob = _problem()
    for wire in ("bf16", "fp16"):
        with pytest.raises(ValueError, match="no wire to compress"):
            plan(prob.op, wire_dtype=wire)
    # the message teaches the fix: it lists the valid values
    with pytest.raises(ValueError, match=r"valid values.*bf16.*fp16.*fp32"):
        PlanConfig(wire_dtype="bf16").validate(distributed=False)


def test_unknown_wire_dtype_lists_valid_values():
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match=r"wire_dtype must be one of.*bf16"):
        plan(prob.op, mesh, wire_dtype="int8")
    with pytest.raises(ValueError, match="wire_dtype must be one of"):
        PlanConfig(wire_dtype="fp64").validate(distributed=True)


def test_plan_config_describe_carries_wire_tag():
    cfg32 = PlanConfig(rfft=True, n1=N1, n2=N2)
    cfg16 = PlanConfig(rfft=True, n1=N1, n2=N2, wire_dtype="bf16")
    assert "wire=" not in cfg32.describe()  # fp32 keeps legacy strings
    assert "wire=bf16" in cfg16.describe()
    # the tag splits serve buckets: describe() must differ
    assert cfg32.describe() != cfg16.describe()


def test_plan_bf16_wire_passes_guard_and_solves():
    """bf16 wire survives the precision guard on a well-scaled operator and
    the solver lands within the documented wire error bound of fp32."""
    from repro.ops.plan import WIRE_ERROR_BOUND

    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    pl16 = plan(prob.op, mesh, n1=N1, n2=N2, wire_dtype="bf16")
    assert pl16.wire_dtype == "bf16"
    assert "wire=bf16" in pl16.config.describe()
    pl32 = plan(prob.op, mesh, n1=N1, n2=N2)
    kw = dict(iters=300, record_every=300, alpha=ALPHA, rho=RHO, sigma=SIGMA)
    x32, _ = solve(prob, "cpadmm", plan=pl32, **kw)
    x16, _ = solve(prob, "cpadmm", plan=pl16, **kw)
    assert _rel(x16, x32) <= WIRE_ERROR_BOUND


def test_wire_dtype_config_and_legacy_kwarg_agree():
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    cfg = PlanConfig(n1=N1, n2=N2, wire_dtype="bf16")
    via_cfg = plan(prob.op, mesh, config=cfg)
    via_kw = plan(prob.op, mesh, n1=N1, n2=N2, wire_dtype="bf16")
    assert via_cfg.config == via_kw.config == cfg


def test_fp16_wire_overflow_triggers_fp32_fallback():
    """ISSUE 8 acceptance: fp16 must either meet the bound or demonstrably
    fall back.  A spectrum scaled past float16's 65504 max overflows the
    inverse-transpose payload, the probe error goes non-finite, and the
    guard demotes the plan to the fp32 wire with a RuntimeWarning."""
    from repro.core.circulant import Circulant

    prob = _problem()
    big = Circulant.from_first_col(prob.op.circ.col * 1e9)
    op_big = PartialCirculant(big, prob.op.omega)
    mesh = make_mesh((1,), ("model",))
    with pytest.warns(RuntimeWarning, match="failed the precision guard"):
        pl = plan(op_big, mesh, n1=N1, n2=N2, wire_dtype="fp16")
    assert pl.wire_dtype == "fp32"  # error-controlled: never silently wrong
    # the fallback plan is the fp32 twin, numerically identical to asking
    # for fp32 outright
    x = jax.random.normal(jax.random.PRNGKey(9), (N,))
    ref = plan(op_big, mesh, n1=N1, n2=N2).matvec(x)
    np.testing.assert_array_equal(np.asarray(pl.matvec(x)), np.asarray(ref))


# ---------------------------------------------------------------------------
# hierarchical (host, device) transform axis — validation + describe
# ---------------------------------------------------------------------------


def test_local_plan_rejects_hier_axes_loudly():
    """The single validation site refuses hier_axes without a mesh, in the
    valid-values-listed error style."""
    prob = _problem()
    with pytest.raises(ValueError, match="no mesh axes to factor"):
        plan(prob.op, hier_axes=(2, 2))
    with pytest.raises(ValueError, match=r"valid values: None or a \(H, D\)"):
        PlanConfig(hier_axes=(2, 2)).validate(distributed=False)


def test_malformed_hier_axes_rejected():
    for bad in ((2,), (2, 2, 2), (2, 0), (2.0, 2), "2x2"):
        with pytest.raises(ValueError, match="hier_axes must be a"):
            PlanConfig(hier_axes=bad).validate(distributed=True)


def test_inter_wire_without_hier_rejected():
    """inter_wire_dtype only names the DCN hop of the hierarchical exchange
    — accepting it on a flat plan would silently ignore the knob."""
    prob = _problem()
    mesh = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="inter_wire_dtype"):
        plan(prob.op, mesh, n1=N1, n2=N2, inter_wire_dtype="bf16")
    with pytest.raises(ValueError, match="inter_wire_dtype must be one of"):
        PlanConfig(hier_axes=(2, 2), inter_wire_dtype="int8").validate(
            distributed=True
        )


def test_hier_axes_must_match_mesh_extents():
    """hier_axes=(H, D) is checked against the mesh's actual (host, device)
    extents, and the error names the valid value."""
    from repro.dist.compat import make_hier_mesh

    prob = _problem()
    mesh = make_hier_mesh(1, 1, 1)
    with pytest.raises(ValueError, match=r"valid value: hier_axes=\(1, 1\)"):
        plan(prob.op, mesh, n1=N1, n2=N2, hier_axes=(2, 2))
    # and a mesh without the (host, device) axes teaches the fix
    flat = make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="make_hier_mesh"):
        plan(prob.op, flat, n1=N1, n2=N2, hier_axes=(1, 1))


def test_hier_describe_tags_split_configs():
    base = PlanConfig(rfft=True, n1=N1, n2=N2)
    hier = PlanConfig(rfft=True, n1=N1, n2=N2, hier_axes=(2, 4),
                      axis_name=("host", "device"))
    tflat = PlanConfig(rfft=True, n1=N1, n2=N2, axis_name=("host", "device"))
    iw = PlanConfig(rfft=True, n1=N1, n2=N2, hier_axes=(2, 4),
                    axis_name=("host", "device"), inter_wire_dtype="bf16")
    assert "hier=" not in base.describe()
    assert "hier=2x4" in hier.describe()
    assert "hier=flat" in tflat.describe()  # factored axis, one flat a2a
    assert "inter_wire=bf16" in iw.describe()
    assert len({c.describe() for c in (base, hier, tflat, iw)}) == 4


def test_hier_config_round_trips_through_json():
    cfg = PlanConfig(rfft=True, n1=N1, n2=N2, hier_axes=(2, 4),
                     axis_name=("host", "device"), inter_wire_dtype="bf16")
    again = PlanConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
    assert again == cfg
    assert isinstance(again.hier_axes, tuple)
    assert isinstance(again.axis_name, tuple)


def test_hier_plan_solves_on_degenerate_mesh():
    """The 1x1 (host, device) mesh runs the full hier code path in the fast
    lane; the solve must match the flat plan bit-for-bit (no inter hop to
    demote, no intra shuffle to get wrong)."""
    from repro.dist.compat import make_hier_mesh

    prob = _problem()
    flat = plan(prob.op, make_mesh((1,), ("model",)), n1=N1, n2=N2, rfft=True)
    hier = plan(prob.op, make_hier_mesh(1, 1, 1), n1=N1, n2=N2, rfft=True,
                hier_axes=(1, 1))
    assert hier.hier and hier.axis_name == ("host", "device")
    kw = dict(iters=40, record_every=40, alpha=ALPHA, rho=RHO, sigma=SIGMA)
    xf, _ = solve(prob, "cpadmm", plan=flat, **kw)
    xh, _ = solve(prob, "cpadmm", plan=hier, **kw)
    assert jnp.array_equal(xf, xh)

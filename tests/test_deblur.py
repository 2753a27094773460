"""Compressed deblurring application tests (paper Sec. 7)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import RecoveryProblem, solve
from repro.core.circulant import Circulant
from repro.core.deblur import (
    blurred_observation,
    build_deblur_plan,
    build_deblur_problem,
    build_multiframe_deblur_problem,
    deblur_metrics,
    recovered_image,
)
from repro.data.synthetic import starfield

SOLVE_KW = dict(alpha=1e-3, rho=0.01, sigma=0.01)

# The golden pins and the multiframe bound were recorded with data drawn
# under JAX's original threefry stream.  JAX 0.9 switched the default
# (``jax_threefry_partitionable=True``), which redraws every starfield,
# operator and subset from the same keys; the tests that pin values build
# their data inside this context so the pinned instances stay the same.
recorded_stream = functools.partial(jax.threefry_partitionable, False)


def _rel(got, want):
    got, want = jnp.asarray(got), jnp.asarray(want)
    return float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30))


@pytest.fixture(scope="module")
def small_problem():
    img = starfield(jax.random.PRNGKey(0), h=32, w=32, density=0.08, n_blobs=3)
    return build_deblur_problem(
        jax.random.PRNGKey(1), img, blur_order=5, subsample=0.5, sensing="romberg"
    )


def test_operator_is_joint_sense_blur(small_problem):
    """A = P (C B) — verified against the dense product on a tiny image."""
    p = small_problem
    n = p.image.size
    # dense check on a random vector instead of full materialization (n=1024)
    x = jax.random.normal(jax.random.PRNGKey(2), (n,))
    via_parts = p.op.circ.matvec(x)
    # the joint circulant must equal sense-after-blur applied sequentially:
    # spec(joint) = spec(C) * spec(B); verify with an independent blur apply
    blurred = p.blur.matvec(x)
    sense_spec = p.op.circ.spec / jnp.where(p.blur.spec == 0, 1.0, p.blur.spec)
    sense = Circulant.from_spectrum(sense_spec, n)
    np.testing.assert_allclose(
        np.asarray(sense.matvec(blurred)), np.asarray(via_parts), atol=5e-3
    )


def test_measurements_are_of_blurred_image(small_problem):
    p = small_problem
    x = p.image.reshape(-1)
    direct = jnp.take(p.op.circ.matvec(x), p.op.omega, axis=-1)
    np.testing.assert_allclose(np.asarray(p.y), np.asarray(direct), atol=1e-5)


def test_blur_smears_forward():
    img = jnp.zeros((8, 8)).at[3, 3].set(1.0)
    prob = build_deblur_problem(jax.random.PRNGKey(0), img, blur_order=4)
    b = np.asarray(blurred_observation(prob)).reshape(-1)
    flat = np.zeros(64)
    flat[3 * 8 + 3] = 1.0
    # order-4 moving average along the raster, circular
    expect = np.zeros(64)
    for l in range(4):
        expect[(3 * 8 + 3 - l) % 64] += 0.25
    np.testing.assert_allclose(b, expect, atol=1e-6)


def test_compressed_deblurring_recovers(small_problem):
    """End-to-end Sec. 7: recover a sharp image from compressed blurred
    measurements; normalized MSE must land in the paper's 1e-4 order."""
    p = small_problem
    prob = RecoveryProblem(op=p.op, y=p.y, x_true=p.image.reshape(-1))
    x, tr = solve(prob, "cpadmm", iters=800, record_every=800, alpha=1e-3, rho=0.01, sigma=0.01)
    m = deblur_metrics(p, x)
    assert float(m["normalized_mse"]) < 5e-3
    img = recovered_image(p, x)
    assert img.shape == p.image.shape
    # the recovery must beat simply using the blurred observation
    blurred = blurred_observation(p)
    blurred_nmse = float(
        jnp.mean((blurred - p.image) ** 2) / jnp.mean(p.image**2)
    )
    assert float(m["normalized_mse"]) < blurred_nmse / 5


# Golden values recorded per case (starfield key 0, problem key 1, 800
# CPADMM iterations): (psnr_db, normalized_mse, rel_err).  A solver refactor
# that silently degrades recovery shows up here as a PSNR drop / error rise
# even while the looser end-to-end bound above still passes.  Bands are
# ~10-15% wide to absorb cross-platform float accumulation differences —
# not algorithmic drift, which moves these numbers by integer factors.
GOLDEN = {
    # the canonical paper-regime case (the original golden pin)
    ("romberg", 32, 32): (45.00, 6.67e-4, 2.58e-2),
    # odd, non-square extents: n = 31*33 exercises the odd-n rfft bookkeeping
    ("romberg", 31, 33): (43.19, 1.01e-3, 3.18e-2),
    # paper-faithful gaussian sensing (worse conditioning, lower quality —
    # pinned all the same so a conditioning regression is loud)
    ("gaussian", 32, 32): (33.94, 8.49e-3, 9.22e-2),
}


def _golden_problem(sensing, h, w):
    with recorded_stream():
        img = starfield(jax.random.PRNGKey(0), h=h, w=w, density=0.08, n_blobs=3)
        return build_deblur_problem(
            jax.random.PRNGKey(1), img, blur_order=5, subsample=0.5,
            sensing=sensing,
        )


def _check_golden(p, x, case):
    golden_psnr, golden_nmse, golden_rel = GOLDEN[case]
    m = deblur_metrics(p, x)
    rel = _rel(x, p.image.reshape(p.image.shape[:-2] + (-1,)))
    assert float(m["psnr_db"]) > golden_psnr - 0.5, case
    assert float(m["normalized_mse"]) < golden_nmse * 1.15, case
    assert rel < golden_rel * 1.15, case
    # and the pin is two-sided: suspicious *improvements* need a human look
    assert float(m["psnr_db"]) < golden_psnr + 3.0, case


@pytest.mark.parametrize("sensing,h,w", sorted(GOLDEN))
def test_deblur_golden_regression(sensing, h, w):
    """Pin the recovery quality of the Sec. 7 pipeline on fixed seeds,
    across sensing families and odd non-square image extents."""
    p = _golden_problem(sensing, h, w)
    prob = RecoveryProblem(op=p.op, y=p.y, x_true=p.image.reshape(-1))
    x, _ = solve(prob, "cpadmm", iters=800, record_every=800, **SOLVE_KW)
    _check_golden(p, x, (sensing, h, w))


# Same harness, richer PSF families (repro.core.circulant gaussian/airy):
# (psnr_db, normalized_mse, rel_err) recorded at 800 CPADMM iterations.  The
# airy PSF concentrates energy in a tight core (easy deconvolution, high
# PSNR); the gaussian sigma=1 spreads it (harder, lower) — both pinned so a
# PSF-spectrum regression is loud in either direction.
GOLDEN_PSF = {
    ("gaussian", 1.0): (43.24, 1.00e-3, 3.16e-2),
    ("airy", 2.0): (53.19, 1.01e-4, 1.01e-2),
}


@pytest.mark.parametrize("blur_kind,order", sorted(GOLDEN_PSF))
def test_deblur_golden_psf_families(blur_kind, order):
    """The Sec. 7 pipeline accepts the astronomy-realistic PSF families end
    to end — composed through the same joint operator and golden-pinned
    like the moving-average cases, through the planned (rfft) path."""
    from repro.dist.compat import make_mesh

    with recorded_stream():
        img = starfield(jax.random.PRNGKey(0), h=32, w=32, density=0.08,
                        n_blobs=3)
        p = build_deblur_problem(
            jax.random.PRNGKey(1), img, blur_order=order, subsample=0.5,
            sensing="romberg", blur_kind=blur_kind,
        )
    prob = RecoveryProblem(op=p.op, y=p.y, x_true=img.reshape(-1))
    x_ref, _ = solve(prob, "cpadmm", iters=800, record_every=800, **SOLVE_KW)
    golden_psnr, golden_nmse, golden_rel = GOLDEN_PSF[(blur_kind, order)]
    m = deblur_metrics(p, x_ref)
    rel = _rel(x_ref, img.reshape(-1))
    assert float(m["psnr_db"]) > golden_psnr - 0.5, (blur_kind, order)
    assert float(m["psnr_db"]) < golden_psnr + 3.0, (blur_kind, order)
    assert float(m["normalized_mse"]) < golden_nmse * 1.15
    assert rel < golden_rel * 1.15
    # the planned lowering composes the same PSF spectrum (1e-5 parity)
    pl = build_deblur_plan(p, make_mesh((1,), ("model",)), rfft=True)
    x_pl, _ = solve(prob, "cpadmm", iters=800, record_every=800, plan=pl,
                    **SOLVE_KW)
    assert _rel(x_pl, x_ref) <= 1e-5


def test_make_blur_dispatch_validates():
    from repro.core.deblur import _make_blur

    with pytest.raises(ValueError, match="blur_kind"):
        build_deblur_problem(jax.random.PRNGKey(0), jnp.zeros((8, 8)),
                             blur_kind="box")
    # each family's own loud width validation surfaces through the builder
    for kind in ("moving-average", "gaussian", "airy"):
        with pytest.raises(ValueError):
            _make_blur(64, kind, 0, jnp.float32)
        with pytest.raises(ValueError):
            _make_blur(64, kind, 65, jnp.float32)


# ---------------------------------------------------------------------------
# the PSF families themselves (repro.core.circulant builders)
# ---------------------------------------------------------------------------


def test_gaussian_blur_kernel():
    from repro.core.circulant import gaussian_blur

    B = gaussian_blur(32, 2.0)
    col = np.asarray(B.col)
    assert col.sum() == pytest.approx(1.0, abs=1e-6)  # flux-preserving
    assert col[0] == col.max()  # peak at zero lag
    np.testing.assert_allclose(col[1:], col[1:][::-1], atol=1e-7)  # symmetric
    # circular distance: col[j] depends on min(j, n-j) only
    assert col[1] == pytest.approx(col[31], abs=1e-7)
    # monotone decay over the first half
    assert (np.diff(col[:16]) <= 1e-9).all()


def test_airy_blur_kernel():
    from repro.core.circulant import airy_blur

    B = airy_blur(64, 4.0)
    col = np.asarray(B.col)
    assert col.sum() == pytest.approx(1.0, abs=1e-6)
    assert col[0] == col.max()
    np.testing.assert_allclose(col[1:], col[1:][::-1], atol=1e-7)
    # the first null lands at the radius: intensity there ~ 0
    assert col[4] < col[0] * 1e-4
    # truncated past 4 radii (finite support keeps the PSF compact)
    assert col[20] == 0.0
    # the sidelobe between the first and second null is nonzero (it is an
    # airy pattern, not a disk): ~1.75% of the peak at u ~ 5.14
    assert col[5] > 0.0


def test_bessel_j1_quadrature():
    """The fixed midpoint quadrature for J1 is accurate to float32 over the
    argument range the airy PSF evaluates (u in [0, ~15.3])."""
    from repro.core.circulant import _bessel_j1

    # reference values (Abramowitz & Stegun / scipy.special.j1)
    for x, want in ((0.5, 0.2422684577), (1.0, 0.4400505857),
                    (3.8317, 0.0000074570), (7.0155, -1.4375e-5),
                    (10.0, 0.0434727462)):
        got = float(_bessel_j1(jnp.asarray(x)))
        assert got == pytest.approx(want, abs=5e-5), x


def test_psf_builders_validate_width():
    """gaussian/airy port moving_average_blur's loud 0 < width <= n rule."""
    from repro.core.circulant import airy_blur, gaussian_blur

    for build, name in ((gaussian_blur, "sigma"), (airy_blur, "radius")):
        with pytest.raises(ValueError, match=name):
            build(8, 0)
        with pytest.raises(ValueError, match=name):
            build(8, -1.5)
        with pytest.raises(ValueError, match=name):
            build(8, 9.0)
        build(8, 8.0)  # width == n is the legal extreme


def test_shift_circulant_is_roll():
    from repro.core.circulant import shift_circulant

    x = jnp.arange(8.0)
    for s in (0, 1, 3, -2, 11):
        S = shift_circulant(8, s)
        np.testing.assert_allclose(
            np.asarray(S.matvec(x)), np.asarray(jnp.roll(x, s)), atol=1e-6
        )
        # adjoint is the inverse shift (S is a permutation)
        np.testing.assert_allclose(
            np.asarray(S.rmatvec(x)), np.asarray(jnp.roll(x, -s)), atol=1e-6
        )
    with pytest.raises(ValueError, match="n"):
        shift_circulant(0, 1)


def test_psf_families_compose_with_sensing():
    """Every PSF family rides compose_sensing_blur into the joint operator
    the deblur pipeline plans over."""
    from repro.core.circulant import (
        airy_blur,
        compose_sensing_blur,
        gaussian_blur,
        gaussian_circulant,
    )

    C = gaussian_circulant(jax.random.PRNGKey(2), 32)
    for B in (gaussian_blur(32, 1.5), airy_blur(32, 2.0)):
        A = compose_sensing_blur(C, B)
        np.testing.assert_allclose(
            np.asarray(A.to_dense()),
            np.asarray(C.to_dense()) @ np.asarray(B.to_dense()),
            atol=1e-3,
        )


# ---------------------------------------------------------------------------
# the planned (execution-plan) deblur path — ISSUE 5 tentpole
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rfft", [False, True])
def test_deblur_planned_matches_single_device(small_problem, rfft):
    """Distributed (planned) deblur == the single-device solve at 1e-5 rel:
    the composed operator lowered through ops.plan on a 1-device mesh (the
    8-device variant rides tests/dist_progs/deblur_prog.py)."""
    from repro.dist.compat import make_mesh

    p = small_problem
    prob = RecoveryProblem(op=p.op, y=p.y, x_true=p.image.reshape(-1))
    x_ref, _ = solve(prob, "cpadmm", iters=300, record_every=300, **SOLVE_KW)
    pl = build_deblur_plan(p, make_mesh((1,), ("model",)), rfft=rfft)
    # deblur-aware defaults: the four-step layout is the image's own grid
    assert (pl.n1, pl.n2) == p.image.shape
    x_dist, _ = solve(prob, "cpadmm", iters=300, record_every=300,
                      plan=pl, **SOLVE_KW)
    assert _rel(x_dist, x_ref) <= 1e-5


@pytest.mark.parametrize("sensing,h,w", [("romberg", 32, 32), ("romberg", 31, 33)])
def test_deblur_golden_regression_planned(sensing, h, w):
    """The golden pins hold through the planned path too (rfft layout), and
    the planned solve tracks the core one at 1e-5 — covering odd extents,
    where the half-spectrum padding logic is busiest."""
    from repro.dist.compat import make_mesh

    p = _golden_problem(sensing, h, w)
    prob = RecoveryProblem(op=p.op, y=p.y, x_true=p.image.reshape(-1))
    x_ref, _ = solve(prob, "cpadmm", iters=800, record_every=800, **SOLVE_KW)
    pl = build_deblur_plan(p, make_mesh((1,), ("model",)), rfft=True)
    x, _ = solve(prob, "cpadmm", iters=800, record_every=800, plan=pl, **SOLVE_KW)
    assert _rel(x, x_ref) <= 1e-5
    _check_golden(p, x, (sensing, h, w))


def test_multiframe_deblur_golden_planned():
    """The multiframe golden PSNR pin through the planned path: every frame
    of a 4-frame stack recovers at >= 45 dB from one batched distributed
    solve (values recorded: [46.02, 48.23, 45.31, 48.46] dB)."""
    from repro.dist.compat import make_mesh

    F = 4
    imgs = jnp.stack(
        [starfield(jax.random.PRNGKey(i), h=32, w=32, density=0.05, n_blobs=2)
         for i in range(F)]
    )
    p = build_multiframe_deblur_problem(
        jax.random.PRNGKey(1), imgs, blur_order=5, subsample=0.5,
        sensing="romberg",
    )
    prob = RecoveryProblem(op=p.op, y=p.y, x_true=imgs.reshape(F, -1))
    pl = build_deblur_plan(p, make_mesh((1,), ("model",)), rfft=True)
    x, _ = solve(prob, "cpadmm", iters=800, record_every=800, plan=pl, **SOLVE_KW)
    psnr = np.asarray(deblur_metrics(p, x)["psnr_db"])
    assert psnr.shape == (F,)
    assert (psnr >= 45.0).all(), psnr
    assert (psnr <= 52.0).all(), psnr  # two-sided: improvements need a look


def test_build_deblur_plan_local_and_batch_defaults():
    """mesh=None is the identity lowering; a (data, model) mesh auto-shards
    a frame stack over the data axis."""
    from repro.dist.compat import make_mesh

    imgs = jnp.stack(
        [starfield(jax.random.PRNGKey(i), h=16, w=16, density=0.08, n_blobs=2)
         for i in range(2)]
    )
    p = build_multiframe_deblur_problem(
        jax.random.PRNGKey(4), imgs, blur_order=3, subsample=0.6, sensing="romberg"
    )
    pl_local = build_deblur_plan(p)
    assert not pl_local.is_distributed and pl_local.operator is p.op
    pl = build_deblur_plan(p, make_mesh((1, 1), ("data", "model")), rfft=True)
    assert pl.is_distributed and pl.batch_axis == "data"
    assert (pl.n1, pl.n2) == (16, 16)


def test_multiframe_deblur_batched_recovery():
    """A (F, H, W) stack through one shared optic recovers per frame with a
    single batched solve; metrics come back with the frame axis."""
    F = 3
    with recorded_stream():
        imgs = jnp.stack(
            [starfield(jax.random.PRNGKey(10 + i), h=16, w=16, density=0.08,
                       n_blobs=2)
             for i in range(F)]
        )
        p = build_multiframe_deblur_problem(
            jax.random.PRNGKey(4), imgs, blur_order=3, subsample=0.6,
            sensing="romberg",
        )
    assert p.y.shape == (F, p.op.m)
    prob = RecoveryProblem(op=p.op, y=p.y, x_true=imgs.reshape(F, -1))
    x, _ = solve(prob, "cpadmm", iters=500, record_every=500,
                 alpha=1e-3, rho=0.01, sigma=0.01)
    m = deblur_metrics(p, x)
    assert m["normalized_mse"].shape == (F,)
    assert (np.asarray(m["normalized_mse"]) < 5e-3).all()
    img = recovered_image(p, x)
    assert img.shape == imgs.shape
    assert blurred_observation(p).shape == imgs.shape
    # batched == per-frame sequential (same operator, independent frames)
    for f in range(F):
        single = RecoveryProblem(op=p.op, y=p.y[f], x_true=imgs[f].reshape(-1))
        xs, _ = solve(single, "cpadmm", iters=500, record_every=500,
                      alpha=1e-3, rho=0.01, sigma=0.01)
        rel = float(jnp.linalg.norm(x[f] - xs) / (jnp.linalg.norm(xs) + 1e-30))
        assert rel <= 1e-6, f


def test_build_deblur_problem_rejects_stacks():
    """Batched input used to die with a bare tuple-unpack error; now both
    builders point at each other with a clear message."""
    imgs = jnp.zeros((2, 8, 8))
    with pytest.raises(ValueError, match="build_multiframe_deblur_problem"):
        build_deblur_problem(jax.random.PRNGKey(0), imgs)
    with pytest.raises(ValueError, match="build_deblur_problem"):
        build_multiframe_deblur_problem(jax.random.PRNGKey(0), jnp.zeros((8, 8)))


def test_deblur_metrics_degenerate_frame_psnr():
    """An all-zero frame has no peak to reference: PSNR is the -inf sentinel
    (not the misleading finite number an epsilon'd peak produced), and the
    batch shape survives."""
    lit = starfield(jax.random.PRNGKey(0), h=8, w=8, density=0.3, n_blobs=2)
    imgs = jnp.stack([lit, jnp.zeros((8, 8))])
    p = build_multiframe_deblur_problem(
        jax.random.PRNGKey(1), imgs, blur_order=2, subsample=0.8, sensing="romberg"
    )
    m = deblur_metrics(p, jnp.zeros((2, 64)))
    assert m["psnr_db"].shape == (2,)
    assert np.isfinite(float(m["psnr_db"][0]))
    assert float(m["psnr_db"][1]) == -np.inf
    # a perfect reconstruction of a lit frame still reports a huge finite PSNR
    m2 = deblur_metrics(p, imgs.reshape(2, -1))
    assert np.isfinite(float(m2["psnr_db"][0])) and float(m2["psnr_db"][0]) > 100.0


def test_starfield_statistics():
    img = starfield(jax.random.PRNGKey(3), h=64, w=64, density=0.1, n_blobs=4)
    frac_lit = float(jnp.mean(img > 0))
    assert 0.05 < frac_lit < 0.5  # sparse-ish, blobs add some support
    assert float(img.min()) >= 0.0 and float(img.max()) <= 1.0


def test_multiframe_deblur_golden_bf16_wire():
    """ISSUE 8 acceptance: the 4-frame golden deblur stack recovers at
    >= 45 dB PSNR per frame with the bf16 wire — halving the transpose
    all-to-all bytes costs no visible reconstruction quality (values
    recorded: [45.91, 48.18, 45.32, 48.05] dB, within 0.4 dB of the
    fp32-wire pins)."""
    from repro.dist.compat import make_mesh

    F = 4
    imgs = jnp.stack(
        [starfield(jax.random.PRNGKey(i), h=32, w=32, density=0.05, n_blobs=2)
         for i in range(F)]
    )
    p = build_multiframe_deblur_problem(
        jax.random.PRNGKey(1), imgs, blur_order=5, subsample=0.5,
        sensing="romberg",
    )
    prob = RecoveryProblem(op=p.op, y=p.y, x_true=imgs.reshape(F, -1))
    pl = build_deblur_plan(p, make_mesh((1,), ("model",)), rfft=True,
                           wire_dtype="bf16")
    assert pl.wire_dtype == "bf16"  # the precision guard accepted the wire
    x, _ = solve(prob, "cpadmm", iters=800, record_every=800, plan=pl, **SOLVE_KW)
    psnr = np.asarray(deblur_metrics(p, x)["psnr_db"])
    assert psnr.shape == (F,)
    assert (psnr >= 45.0).all(), psnr
    assert (psnr <= 52.0).all(), psnr

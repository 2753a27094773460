"""Production recovery launcher: batched CS recovery with checkpoint/restart.

    PYTHONPATH=src python -m repro.launch.recover --n 65536 --batch 4 \
        --method cpadmm --iters 600 --ckpt-dir artifacts/recover_ckpt

Runs the paper's workload as a restartable job: a batch of compressively
sensed signals (one shared sensing operator, ``--batch`` independent
signals) is recovered with the selected solver, checkpointing solver state
every chunk.  ``--tol`` switches from the fixed iteration budget to the
tolerance-driven driver: convergence is then tracked *per signal* (early
finishers freeze while the rest iterate) and the per-signal iteration
counts are reported.

``--mesh`` routes the same job through the execution-plan layer
(``repro.ops.plan``): each signal is sharded over the mesh's model axis via
the four-step FFT and *the same drivers* run — every ``--method`` works
distributed, tolerance-stopped, and checkpointable.  ``--mesh 8`` shards
signals over 8 devices; ``--mesh 2x4`` additionally shards the batch over a
2-way data axis.  ``--fake-devices N`` forces N XLA host devices so the
distributed path can be exercised on a CPU box.

``--deblur`` swaps the workload to the paper's flagship Sec. 7 scenario —
compressed-domain deblurring: ``--batch`` starfield frames of
``--size`` x ``--size`` are sensed through one shared joint operator
``A = P (C B)`` (order-``--blur-order`` raster blur composed with the
``--sensing`` circulant, m = n/2) and one batched solve jointly undoes
sub-sampling and blur.  The same ``--mesh`` / ``--rfft`` / ``--overlap`` /
``--tol`` / checkpointing flags apply — the deblur operator lowers through
``repro.core.deblur.build_deblur_plan``, so e.g.

    PYTHONPATH=src python -m repro.launch.recover --deblur --batch 4 \
        --size 64 --blur-order 5 --mesh 2x4 --rfft --fake-devices 8

deblurs a four-frame stack distributed over a (data, model) mesh.
Per-frame PSNR / normalized MSE are reported after the solve.
"""

from __future__ import annotations

import argparse
import sys

if __name__ == "__main__":  # --fake-devices must land before jax imports
    _pre = argparse.ArgumentParser(add_help=False)
    _pre.add_argument("--fake-devices", type=int, default=0)
    _n, _ = _pre.parse_known_args()
    if _n.fake_devices:
        from repro.launch.env import append_flag

        append_flag("XLA_FLAGS",
                    f"--xla_force_host_platform_device_count={_n.fake_devices}")

import time

import jax
import jax.numpy as jnp

from repro.ckpt import checkpoint as ckpt
from repro.core import (
    RecoveryProblem,
    partial_gaussian_circulant,
    solve_checkpointed,
    solve_until,
)
from repro.data.synthetic import paper_regime, sparse_signal
from repro.launch.env import configure_compile_cache

METHODS = ("cpadmm", "ista", "fista")


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="batched CS recovery launcher (see module docstring)"
    )
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--method", default="cpadmm", choices=METHODS,
                    metavar=f"{{{','.join(METHODS)}}}",
                    help="solver method; every method runs on every backend")
    ap.add_argument("--iters", type=int, default=600)
    ap.add_argument("--chunk", type=int, default=100)
    ap.add_argument("--alpha", type=float, default=1e-4)
    ap.add_argument("--tol", type=float, default=0.0,
                    help="run to per-signal convergence (relative-change "
                         "tolerance) instead of a fixed --iters budget")
    ap.add_argument("--deblur", action="store_true",
                    help="compressed-domain deblurring workload (Sec. 7): "
                         "--batch starfield frames sensed through one joint "
                         "A = P (C B) operator; reports per-frame PSNR")
    ap.add_argument("--blur-order", type=float, default=5,
                    help="blur width knob (with --deblur): raster length L "
                         "for moving-average, sigma for gaussian, first-null "
                         "radius for airy")
    ap.add_argument("--blur-kind", default="moving-average",
                    choices=("moving-average", "gaussian", "airy"),
                    help="PSF family for --deblur (repro.core.circulant)")
    ap.add_argument("--size", type=int, default=64,
                    help="frame extent: n = size*size (with --deblur)")
    ap.add_argument("--sensing", default="romberg",
                    choices=("gaussian", "romberg"),
                    help="sensing circulant family (with --deblur)")
    ap.add_argument("--prior", default="l1",
                    choices=("l1", "tv", "wavelet", "nonneg-l1"),
                    help="recovery prior (repro.ops.prox): l1 is the paper's "
                         "soft threshold (fused kernels stay on); tv is "
                         "anisotropic 2-D total variation (frames must be "
                         "square: --size with --deblur, sqrt(n) otherwise); "
                         "wavelet thresholds orthogonal Haar detail "
                         "coefficients; nonneg-l1 adds a positivity "
                         "constraint")
    ap.add_argument("--mesh", default=None,
                    help="distributed plan: 'M' (model axis size) or 'DxM' "
                         "(data x model); e.g. --mesh 8 or --mesh 2x4")
    ap.add_argument("--n1", type=int, default=None,
                    help="four-step row count for --mesh (auto near sqrt(n))")
    ap.add_argument("--rfft", action="store_true",
                    help="half-spectrum distributed transforms (with --mesh)")
    ap.add_argument("--overlap", type=int, default=1,
                    help="chunked-transpose overlap factor K (with --mesh)")
    ap.add_argument("--wire-dtype", default="fp32",
                    choices=("fp32", "bf16", "fp16"),
                    help="transpose all-to-all payload precision (with "
                         "--mesh): bf16/fp16 halve the wire bytes; lossy "
                         "wires are guarded by an fp32 fallback past the "
                         "plan layer's precision bound")
    ap.add_argument("--tune", nargs="?", const="model", default=None,
                    choices=("model", "measure"),
                    help="autotune the plan config (repro.ops.tune): bare "
                         "--tune ranks candidates by the HLO cost model; "
                         "--tune measure additionally wall-clocks the top "
                         "picks.  Explicit --rfft/--overlap/--n1 become "
                         "pins; the winner is cached in "
                         "artifacts/plan_cache.json (REPRO_PLAN_CACHE)")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="force N XLA host devices (must be the first thing "
                         "jax sees; honored when run as a script)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: "
                         "artifacts/recover_ckpt, or "
                         "artifacts/recover_deblur_ckpt with --deblur — kept "
                         "separate so one workload never resumes from the "
                         "other's solver state)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def parse_mesh(mesh_arg: str | None):
    """CLI mesh spec -> (mesh, batch_axis): None, 'M', or 'DxM'."""
    if mesh_arg is None:
        return None, None
    from repro.dist.compat import make_mesh

    shape = tuple(int(t) for t in mesh_arg.lower().split("x"))
    if len(shape) == 1:
        return make_mesh(shape, ("model",)), None
    if len(shape) == 2:
        return make_mesh(shape, ("data", "model")), "data"
    raise ValueError(f"--mesh must be 'M' or 'DxM', got {mesh_arg!r}")


def make_prior(prior: str, n: int, size: int | None = None):
    """CLI ``--prior`` name -> a ``repro.ops.prox`` instance (None for l1).

    l1 maps to None so the default path keeps its fused-kernel lowering and
    bit-exactness pins; tv needs a 2-D extent — ``--size`` under --deblur,
    else the signal must be square (n a perfect square).
    """
    from repro.ops.prox import NonNegL1Prox, TVProx, WaveletProx

    if prior == "l1":
        return None
    if prior == "nonneg-l1":
        return NonNegL1Prox()
    if prior == "wavelet":
        return WaveletProx()
    if prior == "tv":
        if size is not None:
            return TVProx(shape=(size, size))
        side = int(round(n ** 0.5))
        if side * side != n:
            raise SystemExit(
                f"--prior tv needs a square frame: n={n} is not a perfect "
                f"square (use --deblur --size, or a square --n)"
            )
        return TVProx(shape=(side, side))
    raise ValueError(f"unknown prior {prior!r}")


def build_plan(op, mesh_arg: str | None, n1=None, rfft=False, overlap=1,
               config=None, tune=None, batch=None, wire_dtype="fp32",
               prox=None):
    """Lower ``op`` per the CLI mesh spec: None (local) or 'M' / 'DxM'.

    ``config=`` forwards a full ``repro.ops.PlanConfig``; ``tune=`` asks the
    autotuner to pick one, with only the *explicitly set* CLI flags becoming
    pins (a default ``--overlap 1`` must leave the overlap axis open, or
    ``--tune`` could never try K > 1).
    """
    from repro.ops import plan

    mesh, batch_axis = parse_mesh(mesh_arg)
    if tune:
        pins = {}
        if rfft:
            pins["rfft"] = True
        if overlap != 1:
            pins["overlap"] = overlap
        if n1 is not None:
            pins["n1"] = n1
        if batch_axis is not None:
            pins["batch_axis"] = batch_axis
        if wire_dtype != "fp32":
            pins["wire_dtype"] = wire_dtype
        if prox is not None:
            pins["prox"] = prox
        return plan(op, mesh, config=config, tune=tune, batch=batch, **pins)
    if config is not None:
        return plan(op, mesh, config=config)
    if mesh is None:
        # the single validation site rejects --rfft/--overlap/--wire-dtype
        # without --mesh
        return plan(op, rfft=rfft, overlap=overlap, wire_dtype=wire_dtype,
                    prox=prox)
    return plan(op, mesh, n1=n1, rfft=rfft, overlap=overlap,
                batch_axis=batch_axis, wire_dtype=wire_dtype, prox=prox)


def build_deblur_workload(args):
    """The Sec. 7 workload: (problem, plan, deblur_problem) for --deblur.

    ``--batch`` starfield frames sensed through one shared A = P (C B);
    the plan comes from ``build_deblur_plan`` so the composed spectrum is
    sharded once and a 'DxM' mesh puts frames on the data axis.
    """
    from repro.core.deblur import build_deblur_plan, build_multiframe_deblur_problem
    from repro.data.synthetic import starfield

    frames = jnp.stack([
        starfield(jax.random.PRNGKey(args.seed + i), args.size, args.size,
                  density=0.05, n_blobs=2)
        for i in range(args.batch)
    ])
    dp = build_multiframe_deblur_problem(
        jax.random.PRNGKey(args.seed + 1), frames,
        blur_order=args.blur_order, subsample=0.5, sensing=args.sensing,
        blur_kind=args.blur_kind,
    )
    prob = RecoveryProblem(op=dp.op, y=dp.y,
                           x_true=frames.reshape(args.batch, -1))
    mesh, batch_axis = parse_mesh(args.mesh)
    prox = make_prior(args.prior, args.size * args.size, size=args.size)
    if args.tune:
        # pin only explicitly-set flags so the tuner keeps its search space
        pins = {}
        if args.rfft:
            pins["rfft"] = True
        if args.overlap != 1:
            pins["overlap"] = args.overlap
        if args.n1 is not None:
            pins["n1"] = args.n1
        if args.wire_dtype != "fp32":
            pins["wire_dtype"] = args.wire_dtype
        if prox is not None:
            pins["prox"] = prox
        pl = build_deblur_plan(dp, mesh, tune=args.tune, batch=args.batch,
                               **pins)
    else:
        pl = build_deblur_plan(dp, mesh, n1=args.n1,
                               rfft=args.rfft or None,
                               overlap=args.overlap if args.overlap != 1 else None,
                               batch_axis=batch_axis,
                               wire_dtype=(args.wire_dtype
                                           if args.wire_dtype != "fp32"
                                           else None),
                               prox=prox)
    return prob, pl, dp


def report_deblur(dp, x_hat) -> None:
    from repro.core.deblur import deblur_metrics

    m = deblur_metrics(dp, x_hat)
    psnr = jnp.atleast_1d(m["psnr_db"])
    nmse = jnp.atleast_1d(m["normalized_mse"])
    for f in range(psnr.shape[0]):
        print(f"  frame {f}: PSNR {float(psnr[f]):.1f} dB   "
              f"normalized MSE {float(nmse[f]):.2e}")


def main(argv=None):
    args = _parser().parse_args(argv)
    configure_compile_cache()
    if args.ckpt_dir is None:
        args.ckpt_dir = ("artifacts/recover_deblur_ckpt" if args.deblur
                         else "artifacts/recover_ckpt")

    if args.deblur:
        n = args.size * args.size
        prob, pl, dp = build_deblur_workload(args)
        print(f"deblurring batch={args.batch} frames of "
              f"{args.size}x{args.size} (n={n}), blur L={args.blur_order}, "
              f"m={dp.op.m}, sensing={args.sensing}, method={args.method}, "
              f"prior={args.prior}"
              + (f", mesh={args.mesh} (plan API)" if args.mesh else ""))
    else:
        n = args.n
        m, k = paper_regime(n)
        dp = None
        print(f"recovering batch={args.batch} signals, n={n}, m={m}, k={k}, "
              f"method={args.method}, prior={args.prior}"
              + (f", mesh={args.mesh} (plan API)" if args.mesh else ""))

        x_true = sparse_signal(jax.random.PRNGKey(args.seed), n, k,
                               batch=(args.batch,))
        op = partial_gaussian_circulant(jax.random.PRNGKey(args.seed + 1), n, m,
                                        normalize=True)
        prob = RecoveryProblem(op=op, y=op.matvec(x_true), x_true=x_true)
        pl = build_plan(op, args.mesh, n1=args.n1, rfft=args.rfft,
                        overlap=args.overlap, tune=args.tune,
                        batch=args.batch, wire_dtype=args.wire_dtype,
                        prox=make_prior(args.prior, n))
    if args.tune:
        print(f"tuned plan [{args.tune}]: {pl.config.describe()}")
    x_true = prob.x_true

    if args.tol > 0:
        t0 = time.time()
        x_hat, iters_used = solve_until(
            prob, args.method, tol=args.tol, max_iters=args.iters,
            alpha=args.alpha, rho=0.01, sigma=0.01, plan=pl,
        )
        d = x_true - x_hat
        mse = jnp.mean(d * d, axis=-1)
        print(f"finished in {time.time()-t0:.1f}s; per-signal iterations: "
              f"{[int(v) for v in jnp.atleast_1d(iters_used)]}")
        print(f"per-signal MSE: {[f'{v:.2e}' for v in jnp.atleast_1d(mse)]}")
        if dp is not None:
            report_deblur(dp, x_hat)
        return

    restore = None
    latest = ckpt.latest_step(args.ckpt_dir)
    if latest is not None:
        # the saved tree is the solver state; rebuild shape via a fresh stepper
        from repro.core.solvers import make_stepper

        stepper = make_stepper(prob, args.method, alpha=args.alpha,
                               rho=0.01, sigma=0.01, plan=pl)
        shape = jax.eval_shape(stepper.init)
        step_no, state = ckpt.restore(args.ckpt_dir, latest, shape)
        restore = (step_no, state)
        print(f"resumed from iteration {step_no}")

    t0 = time.time()
    x_hat, mse = solve_checkpointed(
        prob,
        args.method,
        iters=args.iters,
        chunk=args.chunk,
        alpha=args.alpha,
        rho=0.01,
        sigma=0.01,
        save_cb=lambda s, st: ckpt.save(args.ckpt_dir, s, jax.device_get(st)),
        restore=restore,
        plan=pl,
    )
    print(f"finished in {time.time()-t0:.1f}s; per-signal MSE: "
          f"{[f'{v:.2e}' for v in jnp.atleast_1d(mse)]}")
    if dp is not None:
        report_deblur(dp, x_hat)


if __name__ == "__main__":
    main(sys.argv[1:])

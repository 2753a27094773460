"""Multi-frame compressed deblurring, distributed, with checkpoint/restart.

    PYTHONPATH=src python examples/deblur_multiframe.py [--frames 4 --size 64]
        [--devices 8 --mesh 2x4 --rfft] [--method cpadmm|ista|fista]

Real astronomical pipelines hand over *stacks* of exposures observed through
the same optics (Herschel/PACS-style map-making), not lone frames.  This
example synthesizes F starfield frames, senses them all through one shared
blur+sensing operator A = P (C B), and recovers the whole stack with a
single batched solve — now lowered through ``build_deblur_plan`` onto a
(data, model) mesh: frames shard over the data axis, each frame's four-step
transforms over the model axis, and the composed spectrum spec(C)·spec(B)
is built and sharded exactly once.

The solve runs through ``solve_checkpointed`` like the production launcher:
it is killed halfway (simulated preemption), restarted from the latest
checkpoint, and the restarted result is verified bit-identical to an
uninterrupted run — the paper's three-hour Sec. 7 recovery as a preemptible
cluster job.  Per-frame PSNR / error metrics and PGM renders come out per
frame as before.
"""

import argparse
import os
import time

if __name__ == "__main__":  # XLA_FLAGS must land before jax imports
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=4)
    ap.add_argument("--size", type=int, default=64)
    ap.add_argument("--iters", type=int, default=600)
    ap.add_argument("--chunk", type=int, default=100)
    ap.add_argument("--blur-order", type=int, default=5)
    ap.add_argument("--method", default="cpadmm",
                    choices=("cpadmm", "ista", "fista"),
                    help="every method runs distributed through the plan")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N fake XLA host devices (0 = real devices)")
    ap.add_argument("--mesh", default=None,
                    help="'M' (model axis) or 'DxM' (data x model); "
                         "default: single-device plan")
    ap.add_argument("--rfft", action="store_true",
                    help="half-spectrum transforms (half the wire bytes)")
    ap.add_argument("--overlap", type=int, default=1,
                    help="chunked-transpose overlap factor K")
    ap.add_argument("--out", default="artifacts/deblur_multiframe")
    args = ap.parse_args()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

import jax  # noqa: E402  (after XLA_FLAGS)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.ckpt import checkpoint as ckpt  # noqa: E402
from repro.core import RecoveryProblem, solve_checkpointed  # noqa: E402
from repro.core.deblur import (  # noqa: E402
    blurred_observation,
    build_deblur_plan,
    build_multiframe_deblur_problem,
    deblur_metrics,
    recovered_image,
)
from repro.core.solvers import make_stepper  # noqa: E402
from repro.data.synthetic import starfield  # noqa: E402
from repro.launch.recover import parse_mesh  # noqa: E402


def save_pgm(path: str, img) -> None:
    arr = np.asarray(jnp.clip(img, 0, 1) * 255).astype(np.uint8)
    h, w = arr.shape
    with open(path, "wb") as f:
        f.write(f"P5 {w} {h} 255\n".encode())
        f.write(arr.tobytes())


def main():
    frames = jnp.stack(
        [starfield(jax.random.PRNGKey(i), args.size, args.size, density=0.10, n_blobs=6)
         for i in range(args.frames)]
    )
    p = build_multiframe_deblur_problem(
        jax.random.PRNGKey(100), frames, blur_order=args.blur_order,
        subsample=0.5, sensing="romberg",
    )
    n = args.size * args.size
    mesh, batch_axis = parse_mesh(args.mesh)
    pl = build_deblur_plan(p, mesh, rfft=args.rfft, overlap=args.overlap,
                           batch_axis=batch_axis)
    print(f"{args.frames} frames of {args.size}x{args.size} (n={n}), "
          f"blur L={args.blur_order}, m={p.op.m}, one shared operator"
          + (f"; mesh={args.mesh} (plan API)" if args.mesh else ""))

    prob = RecoveryProblem(
        op=p.op, y=p.y, x_true=frames.reshape(args.frames, -1)
    )
    kw = dict(alpha=1e-3, rho=0.01, sigma=0.01, plan=pl, chunk=args.chunk)
    ckdir = os.path.join(args.out, "ckpt")
    import shutil

    shutil.rmtree(ckdir, ignore_errors=True)  # stale steps would win "latest"

    def save(step, state):
        ckpt.save(ckdir, step, jax.device_get(state))

    # --- first half of the budget, checkpointing every chunk, then "die"
    half = max(args.chunk, (args.iters // 2) // args.chunk * args.chunk)
    t0 = time.time()
    solve_checkpointed(prob, args.method, iters=half, save_cb=save, **kw)
    print(f"  -- simulated preemption after iter {half}: restarting --")

    # --- restart from the latest checkpoint and run out the full budget
    shape = jax.eval_shape(make_stepper(prob, args.method, **{
        k: v for k, v in kw.items() if k != "chunk"}).init)
    step_no, state = ckpt.restore(ckdir, None, shape)
    assert step_no == half, step_no
    x_hat, _ = solve_checkpointed(
        prob, args.method, iters=args.iters, save_cb=save,
        restore=(step_no, state), **kw,
    )
    x_hat.block_until_ready()
    wall = time.time() - t0

    # --- uninterrupted reference: the restarted stack must be bit-identical
    x_ref, _ = solve_checkpointed(prob, args.method, iters=args.iters, **kw)
    identical = bool((x_hat == x_ref).all())
    print(f"restart-vs-uninterrupted bit-identical: {identical}")
    assert identical

    m = deblur_metrics(p, x_hat)
    print(f"recovered the whole stack in {wall:.1f}s / {args.iters} iters "
          f"({wall / args.frames:.1f}s per frame, one solve + one restart)")
    for f in range(args.frames):
        print(f"  frame {f}: PSNR {float(m['psnr_db'][f]):.1f} dB   "
              f"normalized MSE {float(m['normalized_mse'][f]):.2e}")

    os.makedirs(args.out, exist_ok=True)
    rec = recovered_image(p, x_hat)
    blur = blurred_observation(p)
    for f in range(args.frames):
        save_pgm(os.path.join(args.out, f"frame{f}_original.pgm"), frames[f])
        save_pgm(os.path.join(args.out, f"frame{f}_blurred.pgm"), blur[f])
        save_pgm(os.path.join(args.out, f"frame{f}_recovered.pgm"), rec[f])
    print(f"renders in {args.out}/frame*_{{original,blurred,recovered}}.pgm")


if __name__ == "__main__":
    main()

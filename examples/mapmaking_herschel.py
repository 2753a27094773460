"""Herschel-style multi-observation map-making under the TV prior.

    PYTHONPATH=src python examples/mapmaking_herschel.py [--frames 4 --size 32]
        [--devices 8 --mesh 2x4] [--prior tv|l1]

A space observatory scans the same sky patch at small pointing offsets
(dithering) and the ground segment fuses the exposures into one map.  Under
the paper's compressed-sensing telescope model each offset frame is the
*same* joint operator A = P (C B) applied to a shifted sky — shift
circulants compose into the circulant algebra like everything else
(``repro.core.mapmaking``) — so the whole stack recovers through ONE planned
operator with frames on the batch axis, then co-adds by unshifting:

    y_f = A roll(sky, s_f)      recover z_f jointly      map = mean_f roll(z_f, -s_f)

The blurred, shifted frames are not sparse point fields, so the paper's l1
soft threshold is the wrong prior here; the anisotropic TV prox
(``repro.ops.prox.TVProx``) recovers the map markedly better — the example
prints the PSNR table for both so the gap is a measurement, not a claim.
"""

import argparse
import os
import time

if __name__ == "__main__":  # XLA_FLAGS must land before jax imports
    ap = argparse.ArgumentParser()
    ap.add_argument("--frames", type=int, default=4)
    ap.add_argument("--size", type=int, default=32)
    ap.add_argument("--iters", type=int, default=400)
    ap.add_argument("--blur-sigma", type=float, default=1.5)
    ap.add_argument("--method", default="cpadmm",
                    choices=("cpadmm", "ista", "fista"))
    ap.add_argument("--prior", default="both", choices=("tv", "l1", "both"),
                    help="recovery prior; 'both' prints the comparison table")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N fake XLA host devices (0 = real devices)")
    ap.add_argument("--mesh", default=None,
                    help="'M' (model axis) or 'DxM' (data x model)")
    ap.add_argument("--out", default="artifacts/mapmaking")
    args = ap.parse_args()
    if args.devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()

import jax  # noqa: E402  (after XLA_FLAGS)
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.mapmaking import (  # noqa: E402
    build_mapmaking_plan,
    build_mapmaking_problem,
    solve_mapmaking,
)
from repro.data.synthetic import extended_emission  # noqa: E402
from repro.launch.recover import parse_mesh  # noqa: E402


def save_pgm(path: str, img) -> None:
    arr = np.asarray(jnp.clip(img, 0, 1) * 255).astype(np.uint8)
    h, w = arr.shape
    with open(path, "wb") as f:
        f.write(f"P5 {w} {h} 255\n".encode())
        f.write(arr.tobytes())


def main():
    # extended dust/cloud emission, not a point field: gradient-sparse is the
    # regime where TV earns its keep (run --prior both and read the table)
    sky = extended_emission(jax.random.PRNGKey(7), args.size, args.size,
                            n_sources=3)
    # dither pattern: horizontal and vertical unit offsets around the pointing
    offsets = [0, 1, args.size, args.size + 1, 2, 2 * args.size]
    shifts = offsets[: args.frames]
    prob = build_mapmaking_problem(
        jax.random.PRNGKey(11), sky, shifts,
        blur_order=args.blur_sigma, subsample=0.5,
        sensing="romberg", blur_kind="gaussian",
    )
    mesh, _ = parse_mesh(args.mesh)
    print(f"{len(shifts)} dithered exposures of a {args.size}x{args.size} "
          f"sky, gaussian PSF sigma={args.blur_sigma}, m={prob.deblur.op.m}, "
          f"one shared operator"
          + (f"; mesh={args.mesh} (plan API)" if args.mesh else ""))

    priors = ("tv", "l1") if args.prior == "both" else (args.prior,)
    results = {}
    for prior in priors:
        pl = build_mapmaking_plan(
            prob, mesh, prox="tv" if prior == "tv" else None,
        )
        t0 = time.time()
        z_hat, m = solve_mapmaking(prob, plan=pl, method=args.method,
                                   iters=args.iters, alpha=1e-4)
        m["map"].block_until_ready()
        results[prior] = (m, time.time() - t0)

    print(f"\n  {'prior':<8} {'map PSNR':>10} {'map RMS':>10} {'wall':>8}")
    for prior, (m, wall) in results.items():
        print(f"  {prior:<8} {float(m['psnr_db']):>8.1f} dB "
              f"{float(m['rms']):>10.2e} {wall:>7.1f}s")

    os.makedirs(args.out, exist_ok=True)
    save_pgm(os.path.join(args.out, "sky_true.pgm"), sky)
    for prior, (m, _) in results.items():
        save_pgm(os.path.join(args.out, f"map_{prior}.pgm"), m["map"])
    print(f"\nrenders in {args.out}/{{sky_true,map_*}}.pgm")


if __name__ == "__main__":
    main()

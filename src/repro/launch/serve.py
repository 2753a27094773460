"""Recovery-as-a-service launcher: serve a stream of compressed signals.

    PYTHONPATH=src python -m repro.launch.serve --n 16384 --requests 32 \
        --rate 200 --slots 8

Stands up a :class:`repro.serve.RecoveryServer` — the continuous-batching
dispatcher — and drives it with a seeded synthetic Poisson stream of
heterogeneous recovery requests (mixed tolerances, optional priorities and
deadlines) over one sensing operator.  Converged slots are recycled to
queued requests mid-run, so the batch never drains to its stragglers;
``--compare-static`` additionally serves the identical stream through the
fixed-wave baseline and reports the throughput ratio.

``--mesh`` routes every bucket's engine through the execution-plan layer
(``repro.ops.plan``), same specs as ``repro.launch.recover``: ``--mesh 8``
shards each signal over 8 model-axis devices; ``--fake-devices N`` forces N
XLA host devices so the distributed path runs on a CPU box.  ``--tune``
asks the plan autotuner for each bucket's config — warm runs hit the plan
cache in microseconds.

Reports signals/sec, p50/p99 latency, convergence/expiry counts, and the
recycling statistics per bucket.
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

import jax

METHODS = ("cpadmm", "ista", "fista")


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        description="continuous-batching recovery server (see module docstring)"
    )
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate (requests/second)")
    ap.add_argument("--slots", type=int, default=8,
                    help="batch lanes per bucket engine")
    ap.add_argument("--round-iters", type=int, default=32,
                    help="solver iterations per scheduling round")
    ap.add_argument("--method", default="cpadmm", choices=METHODS,
                    metavar=f"{{{','.join(METHODS)}}}")
    ap.add_argument("--tols", type=float, nargs="+",
                    default=[1e-3, 1e-3, 1e-3, 1e-6],
                    help="per-request tolerance draw (repeat a value to "
                         "weight it; the default is the ragged 3:1 mix)")
    ap.add_argument("--max-iters", type=int, default=2000)
    ap.add_argument("--min-iters", type=int, default=50)
    ap.add_argument("--priorities", type=int, nargs="+", default=[0],
                    help="per-request priority draw (larger runs first)")
    ap.add_argument("--deadline-slack", type=float, default=None,
                    help="per-request deadline = arrival + slack seconds "
                         "(expired requests return flagged partials)")
    ap.add_argument("--alpha", type=float, default=1e-4)
    ap.add_argument("--rho", type=float, default=0.01)
    ap.add_argument("--sigma", type=float, default=0.01)
    ap.add_argument("--compare-static", action="store_true",
                    help="also serve the identical stream through the "
                         "fixed-wave static baseline and report the ratio")
    ap.add_argument("--mesh", default=None,
                    help="distributed engines: 'M' (model axis) or 'DxM'")
    ap.add_argument("--rfft", action="store_true")
    ap.add_argument("--overlap", type=int, default=1)
    ap.add_argument("--n1", type=int, default=None)
    ap.add_argument("--tune", nargs="?", const="model", default=None,
                    choices=("model", "measure"),
                    help="autotune each bucket's plan (warm runs hit the "
                         "plan cache)")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="force N XLA host devices (honored when run as a "
                         "script; must precede jax import)")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None):
    args = _parser().parse_args(argv)
    from repro.launch.env import configure_compile_cache

    configure_compile_cache()

    from repro.core.circulant import partial_gaussian_circulant
    from repro.data.synthetic import paper_regime
    from repro.launch.recover import parse_mesh
    from repro.serve import (
        RecoveryServer,
        WallClock,
        static_batch_serve,
        summarize,
        synthetic_workload,
    )

    mesh, _ = parse_mesh(args.mesh)
    m, k = paper_regime(args.n)
    op = partial_gaussian_circulant(jax.random.PRNGKey(args.seed + 1),
                                    args.n, m, normalize=True)
    reqs = synthetic_workload(
        op, args.requests, rate=args.rate, seed=args.seed, tols=args.tols,
        max_iters=args.max_iters, min_iters=args.min_iters,
        priorities=args.priorities, deadline_slack=args.deadline_slack,
        method=args.method,
    )
    print(f"serving {args.requests} requests, n={args.n}, m={m}, k={k}, "
          f"rate={args.rate}/s, slots={args.slots}, method={args.method}"
          + (f", mesh={args.mesh} (plan API)" if args.mesh else ""))

    tune = args.tune if args.tune else False
    srv = RecoveryServer(mesh=mesh, slots=args.slots,
                         round_iters=args.round_iters, alpha=args.alpha,
                         rho=args.rho, sigma=args.sigma, tune=tune,
                         clock=WallClock())
    srv.warmup(reqs[0])
    srv.clock = WallClock()
    results = srv.serve(reqs)
    s = summarize(results)
    stats = srv.stats()

    print(f"continuous: {s['signals_per_sec']:.2f} signals/s, "
          f"p50 {s['p50_latency_s']:.3f}s, p99 {s['p99_latency_s']:.3f}s, "
          f"converged {s['converged']}/{s['count']}, "
          f"expired {s['expired']}")
    t = stats["total"]
    print(f"  buckets {stats['buckets']}, admitted {t['admitted']}, "
          f"recycled {t['recycled']}, rounds {t['rounds']}, "
          f"slot-iterations {t['slot_iters']}")

    if args.compare_static:
        b = summarize(static_batch_serve(reqs, server=srv,
                                         clock=WallClock()))
        ratio = s["signals_per_sec"] / b["signals_per_sec"]
        print(f"static baseline: {b['signals_per_sec']:.2f} signals/s, "
              f"p50 {b['p50_latency_s']:.3f}s, "
              f"p99 {b['p99_latency_s']:.3f}s")
        print(f"continuous vs static: {ratio:.2f}x signals/s")


if __name__ == "__main__":
    main(sys.argv[1:])

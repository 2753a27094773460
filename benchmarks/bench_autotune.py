"""Plan autotuner vs hand-picked defaults (beyond-paper).

The closing-the-loop benchmark for ``repro.ops.tune``: on the two smoke
workloads — batched CS recovery and multi-frame compressed-domain
deblurring — run the same CPADMM solve under (a) the hand-picked default
plan and (b) the autotuned plan (``tune="measure"``), and report both plus
the tuner's own cost: a cold tune (enumerate + score + measure) and a warm
cache hit (which must be microseconds — the production-run path).

Rows:
    autotune_recovery_default / autotune_recovery_tuned
    autotune_deblur_default   / autotune_deblur_tuned
    autotune_cold_tune        / autotune_warm_cache

The tuned rows' derived field carries the chosen config and the
tuned-vs-default ratio — the acceptance number ROADMAP quotes.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit, pick, time_fn
from repro.launch import roofline

N = pick(65536, 1024)  # 256^2 full
BATCH = pick(4, 2)
ITERS = pick(50, 10)
SIZE = pick(128, 16)  # deblur frame extent
FRAMES = pick(4, 2)
CACHE_PATH = "artifacts/bench_plan_cache.json"


def _solve_us(prob, pl):
    from repro.core import solve

    def run():
        x, _ = solve(prob, "cpadmm", iters=ITERS, record_every=ITERS, plan=pl)
        return x

    return time_fn(jax.jit(run))


def main() -> None:
    from repro.core import RecoveryProblem, partial_gaussian_circulant
    from repro.core.deblur import build_deblur_plan, build_multiframe_deblur_problem
    from repro.data.synthetic import paper_regime, sparse_signal, starfield
    from repro.dist.compat import make_mesh
    from repro.ops import plan
    from repro.ops.tune import PlanCache

    # the tuner scores with its devices' peak rates; this CPU benchmark
    # scores with the v5e entry, named explicitly
    kind = jax.devices()[0].device_kind
    roofline.PEAKS.setdefault(kind, roofline.PEAKS[roofline.V5E])

    # all tunes in this suite share the bench-local store (the deblur path
    # reaches the cache through the env var)
    os.environ["REPRO_PLAN_CACHE"] = CACHE_PATH
    cache = PlanCache()
    cache.clear()  # cold numbers must be cold
    mesh = make_mesh((1,), ("model",))

    # -- batched recovery ---------------------------------------------------
    m, k = paper_regime(N)
    x = sparse_signal(jax.random.PRNGKey(0), N, k, batch=(BATCH,))
    op = partial_gaussian_circulant(jax.random.PRNGKey(1), N, m, normalize=True)
    prob = RecoveryProblem(op=op, y=op.matvec(x), x_true=x)

    default_pl = plan(op, mesh)
    t0 = time.perf_counter()
    tuned_pl = plan(op, mesh, tune="measure", batch=BATCH)
    cold_us = (time.perf_counter() - t0) * 1e6
    t0 = time.perf_counter()
    plan(op, mesh, tune="measure", batch=BATCH)
    warm_us = (time.perf_counter() - t0) * 1e6

    d_us = _solve_us(prob, default_pl)
    t_us = _solve_us(prob, tuned_pl)
    emit("autotune_recovery_default", d_us, f"n={N},batch={BATCH},iters={ITERS}")
    emit(
        "autotune_recovery_tuned", t_us,
        f"vs_default={t_us / d_us:.2f}x,cfg={tuned_pl.config.describe().replace(' ', ';')}",
    )
    emit("autotune_cold_tune", cold_us, "enumerate+score+measure, empty cache")
    emit("autotune_warm_cache", warm_us, "cache hit: no scoring, no compiles")

    # -- multi-frame deblurring --------------------------------------------
    frames = jnp.stack([
        starfield(jax.random.PRNGKey(10 + i), SIZE, SIZE, density=0.05,
                  n_blobs=2)
        for i in range(FRAMES)
    ])
    dp = build_multiframe_deblur_problem(
        jax.random.PRNGKey(2), frames, blur_order=3, subsample=0.5,
        sensing="romberg",
    )
    dprob = RecoveryProblem(op=dp.op, y=dp.y,
                            x_true=frames.reshape(FRAMES, -1))
    d_pl = build_deblur_plan(dp, mesh)
    t_pl = build_deblur_plan(dp, mesh, tune="measure", batch=FRAMES)
    dd_us = _solve_us(dprob, d_pl)
    dt_us = _solve_us(dprob, t_pl)
    emit("autotune_deblur_default", dd_us,
         f"frames={FRAMES},size={SIZE},iters={ITERS}")
    emit(
        "autotune_deblur_tuned", dt_us,
        f"vs_default={dt_us / dd_us:.2f}x,cfg={t_pl.config.describe().replace(' ', ';')}",
    )


if __name__ == "__main__":
    main()

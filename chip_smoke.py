#!/usr/bin/env python3
"""Smoke run of the recovery system's main path on a TPU.

    python chip_smoke.py            # phases (a), (b), (c) on one chip
    python chip_smoke.py --four     # the four-chip comparison, nothing else

Every phase goes through the entry points a user calls —
``repro.ops.plan`` / ``repro.core.deblur.build_deblur_plan`` ->
``repro.core.solvers`` -> ``repro.serve`` — with its data built on the
device by one jitted program from ``--seed``:

  (a) paper regime (Sec. 6): n = 2^24, B = 4, m = n/2, k ~ n/10, partial
      Romberg sensing, CPADMM with tail='jnp' and tail='pallas'; the two
      tails agree, the Pallas program holds compiled kernels, and the
      recovery error against the truth stays under a bound;
  (b) Sec. 7 compressed-domain deblurring: 4 starfield frames of
      1024 x 1024 (the paper's Abell-2744 frame size) through one joint
      sensing+blur operator, with a per-frame PSNR floor;
  (c) serving: a RecoveryServer at n = 2^20 answers 16 requests on 8
      slots with a loose/tight tolerance mix, each result equal to a solo
      ``solve_until`` (<= 1e-5, same iteration count).

``--four`` runs one comparison on a (1, 4) data x model mesh: CPADMM at
n = 2^26 through ``plan(op, mesh)`` (rfft, fp32 wires, overlap 1) against
the same solve on one chip of this process.

Each phase prints its build, compile and run seconds, the device's
``peak_bytes_in_use`` and its checks.  A failed check, or any error, exits
non-zero before the last line; without a TPU the script exits non-zero at
once.  The last line is ``{"ok": true, "device": {...}}``.  The plan cache
lives in ``.chip_smoke/`` (git-ignored), emptied at start; nothing is
restored.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

from repro.launch.env import configure_compile_cache  # noqa: E402

WORK_DIR = os.path.join(ROOT, ".chip_smoke")

# (a) paper regime.  Bounds from a CPU rehearsal of these phase functions
# (seed 0, B = 4): worst relative error 3.05e-4 at n = 2^16 and 2.87e-4 at
# n = 2^20, tails agreeing to 2.8e-7.
A_N, A_BATCH, A_ITERS = 1 << 24, 4, 200
A_ALPHA, A_RHO = 1e-4, 1e-3
A_REL_ERR_BOUND = 1e-3
TAIL_AGREEMENT = 1e-5

# (b) deblurring.  CPU rehearsal (seed 0): worst frame 50.40 dB at 128 px
# and 50.78 dB at 512 px after 200 iterations.
B_SIZE, B_FRAMES, B_ITERS = 1024, 4, 200
B_ALPHA, B_RHO = 1e-4, 1e-2
B_PSNR_FLOOR_DB = 45.0

# (c) serving
C_N, C_REQUESTS, C_SLOTS, C_ROUND_ITERS = 1 << 20, 16, 8, 32
C_TOLS = (1e-3, 1e-5)  # loose / tight
C_MIN_ITERS, C_MAX_ITERS, C_RATE = 50, 1000, 200.0
C_RHO = 1e-2
SERVE_AGREEMENT = 1e-5

# --four
F_N, F_ITERS = 1 << 26, 150
F_ALPHA, F_RHO = 1e-4, 1e-3
DIST_AGREEMENT = 1e-5

KIND = "?"  # device kind, set once JAX has found the chip


def check(ok: bool, what: str) -> None:
    print(f"  check {'ok' if ok else 'FAILED'}: {what}", flush=True)
    if not ok:
        sys.exit(f"chip_smoke: check failed: {what}")


def secs(t: float) -> str:
    return f"{t} s on {KIND}"


def peak_bytes(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"  peak_bytes_in_use {KIND} device {d.id}: "
              f"{peak if peak is not None else 'not reported'}", flush=True)


def timed(fn, *args):
    """(result, seconds) with the result on the device and finished."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def compile_timed(fn, *args):
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    return compiled, time.perf_counter() - t0


def has_kernel(compiled) -> bool:
    """Whether a compiled program runs a Pallas kernel on the chip."""
    return "tpu_custom_call" in compiled.as_text()


def memory_line(compiled) -> str:
    m = compiled.memory_analysis()
    return (f"argument {m.argument_size_in_bytes} B, output "
            f"{m.output_size_in_bytes} B, temp {m.temp_size_in_bytes} B")


def rel(a, b):
    """Per-row ||a - b|| / ||b|| on the device, as host floats."""
    import jax.numpy as jnp
    import numpy as np

    num = jnp.linalg.norm((a - b).reshape(a.shape[0], -1), axis=-1)
    den = jnp.linalg.norm(b.reshape(b.shape[0], -1), axis=-1)
    return np.asarray(num / den)


# ---------------------------------------------------------------------------
# (a) paper regime
# ---------------------------------------------------------------------------


def sparse_truths(key, n: int, k: int, batch: int):
    """(batch, n) signals with exactly k N(0, 1) nonzeros each (Sec. 6),
    with sort-free supports (``random_subset_mask``)."""
    import jax

    from repro.data.synthetic import random_subset_mask

    k_sup, k_val = jax.random.split(key)
    support = jax.vmap(lambda kk: random_subset_mask(kk, n, k))(
        jax.random.split(k_sup, batch))
    return jax.random.normal(k_val, (batch, n)) * support


def build_paper_problem(key, n: int, batch: int):
    """Partial Romberg operator, truths and measurements, all on the
    device; the measurement rows are a sort-free exact m-subset."""
    import jax

    from repro.core import RecoveryProblem
    from repro.core.circulant import PartialCirculant, romberg_circulant
    from repro.data.synthetic import paper_regime, random_subset_indices

    m, k = paper_regime(n)
    k_op, k_rows, k_x = jax.random.split(key, 3)
    op = PartialCirculant(romberg_circulant(k_op, n),
                          random_subset_indices(k_rows, n, m))
    x = sparse_truths(k_x, n, k, batch)
    return RecoveryProblem(op=op, y=op.matvec(x), x_true=x)


def paper_solve(prob, *, tail: str, iters: int, alpha: float, rho: float):
    from repro.core import solve
    from repro.ops import plan

    x, _ = solve(prob, "cpadmm", iters=iters, record_every=iters, alpha=alpha,
                 rho=rho, sigma=rho, plan=plan(prob.op, tail=tail))
    return x


def phase_paper(seed: int, n=A_N, batch=A_BATCH, iters=A_ITERS):
    import jax

    from repro.data.synthetic import paper_regime

    m, k = paper_regime(n)
    print(f"phase a (paper regime, Sec. 6): n={n}, B={batch}, m={m}, k={k}, "
          f"partial Romberg sensing, CPADMM {iters} iterations", flush=True)
    build = jax.jit(build_paper_problem, static_argnums=(1, 2))
    prob, t = timed(build, jax.random.PRNGKey(seed), n, batch)
    print(f"  build (one jitted program, compile included): {secs(t)}")
    xs = {}
    for tail in ("jnp", "pallas"):
        fn = jax.jit(functools.partial(
            paper_solve, tail=tail, iters=iters, alpha=A_ALPHA, rho=A_RHO))
        compiled, tc = compile_timed(fn, prob)
        print(f"  tail={tail} compile: {secs(tc)}; {memory_line(compiled)}")
        if tail == "pallas":
            check(has_kernel(compiled),
                  "tail='pallas' program holds compiled kernels (tpu_custom_call)")
        xs[tail], tr = timed(compiled, prob)
        print(f"  tail={tail} run: {secs(tr)}", flush=True)
    agree = float(rel(xs["pallas"], xs["jnp"]).max())
    check(agree <= TAIL_AGREEMENT,
          f"rel(tail=pallas, tail=jnp) {agree} <= {TAIL_AGREEMENT}")
    for tail in ("jnp", "pallas"):
        err = rel(xs[tail], prob.x_true)
        check(float(err.max()) <= A_REL_ERR_BOUND,
              f"tail={tail} rel err vs x_true per signal {err.tolist()} "
              f"<= {A_REL_ERR_BOUND}")
    peak_bytes(jax.devices()[:1])


# ---------------------------------------------------------------------------
# (b) Sec. 7 compressed-domain deblurring
# ---------------------------------------------------------------------------


def build_deblur_stack(key, size: int, frames: int):
    """Sec. 7 frame stack through one joint operator A = P (C B): the
    paper's order-5 raster blur B, Romberg sensing C, m = n/2 rows kept
    (a sort-free exact subset), all on the device."""
    import jax

    from repro.core.circulant import (
        PartialCirculant,
        compose_sensing_blur,
        moving_average_blur,
        romberg_circulant,
    )
    from repro.core.deblur import DeblurProblem
    from repro.data.synthetic import random_subset_indices, starfield

    n = size * size
    k_img, k_op, k_rows = jax.random.split(key, 3)
    imgs = jax.vmap(lambda k: starfield(k, h=size, w=size))(
        jax.random.split(k_img, frames))
    blur = moving_average_blur(n, 5)
    joint = compose_sensing_blur(romberg_circulant(k_op, n), blur)
    op = PartialCirculant(joint, random_subset_indices(k_rows, n, n // 2))
    return DeblurProblem(op=op, blur=blur, y=op.matvec(imgs.reshape(frames, n)),
                         image=imgs)


def deblur_solve(dp, *, iters: int, alpha: float, rho: float):
    from repro.core import RecoveryProblem, solve
    from repro.core.deblur import build_deblur_plan, deblur_metrics

    frames = dp.image.shape[0]
    prob = RecoveryProblem(op=dp.op, y=dp.y, x_true=dp.image.reshape(frames, -1))
    x, _ = solve(prob, "cpadmm", iters=iters, record_every=iters, alpha=alpha,
                 rho=rho, sigma=rho, plan=build_deblur_plan(dp, tail="pallas"))
    return deblur_metrics(dp, x)["psnr_db"]


def phase_deblur(seed: int, size=B_SIZE, frames=B_FRAMES, iters=B_ITERS):
    import jax

    print(f"phase b (Sec. 7 deblurring): {frames} starfield frames of "
          f"{size}x{size}, raster blur L=5, m=n/2 Romberg sensing, CPADMM "
          f"tail=pallas {iters} iterations", flush=True)
    build = jax.jit(build_deblur_stack, static_argnums=(1, 2))
    dp, t = timed(build, jax.random.PRNGKey(seed + 1), size, frames)
    print(f"  build (one jitted program, compile included): {secs(t)}")
    fn = jax.jit(functools.partial(deblur_solve, iters=iters, alpha=B_ALPHA,
                                   rho=B_RHO))
    compiled, tc = compile_timed(fn, dp)
    print(f"  compile: {secs(tc)}; {memory_line(compiled)}")
    check(has_kernel(compiled),
          "tail='pallas' program holds compiled kernels (tpu_custom_call)")
    psnr, tr = timed(compiled, dp)
    print(f"  run: {secs(tr)}", flush=True)
    psnr = [float(v) for v in psnr]
    check(min(psnr) >= B_PSNR_FLOOR_DB,
          f"per-frame PSNR {psnr} dB >= {B_PSNR_FLOOR_DB} dB")
    peak_bytes(jax.devices()[:1])


# ---------------------------------------------------------------------------
# (c) serving
# ---------------------------------------------------------------------------


def build_serve_workload(key, n: int, requests: int):
    """Normalized partial Gaussian operator (Sec. 6) and ``requests``
    measured sparse signals, on the device in one program."""
    import jax

    from repro.core.circulant import PartialCirculant, gaussian_circulant
    from repro.data.synthetic import paper_regime, random_subset_indices

    m, k = paper_regime(n)
    k_op, k_rows, k_x = jax.random.split(key, 3)
    op = PartialCirculant(gaussian_circulant(k_op, n, normalize=True),
                          random_subset_indices(k_rows, n, m))
    x = sparse_truths(k_x, n, k, requests)
    return op, x, op.matvec(x)


def solo_solve(op, y, tol, min_iters, max_iters, *, config, rho: float):
    from repro.core import RecoveryProblem, solve_until
    from repro.ops import plan

    return solve_until(RecoveryProblem(op=op, y=y), "cpadmm", tol=tol,
                       min_iters=min_iters, max_iters=max_iters, rho=rho,
                       sigma=rho, plan=plan(op, config=config))


def phase_serve(seed: int, n=C_N, requests=C_REQUESTS, slots=C_SLOTS,
                max_iters=C_MAX_ITERS):
    import jax
    import numpy as np

    from repro.data.synthetic import paper_regime
    from repro.ops import PlanConfig
    from repro.serve import RecoveryRequest, RecoveryServer, poisson_times

    m, _ = paper_regime(n)
    print(f"phase c (serving): RecoveryServer n={n}, m={m}, {requests} "
          f"requests on {slots} slots, tolerances {C_TOLS}, tail=pallas",
          flush=True)
    build = jax.jit(build_serve_workload, static_argnums=(1, 2))
    (op, xs, ys), t = timed(build, jax.random.PRNGKey(seed + 2), n, requests)
    print(f"  build (one jitted program, compile included): {secs(t)}")
    cfg = PlanConfig(tail="pallas")
    # half loose, half tight, in seeded order; Poisson arrivals
    tols = np.random.default_rng(seed).permutation(np.resize(C_TOLS, requests))
    arrivals = poisson_times(seed, requests, C_RATE)
    reqs = [RecoveryRequest(
        request_id=f"req-{i:04d}", op=op, y=ys[i], x_true=xs[i],
        tol=float(tols[i]), min_iters=C_MIN_ITERS, max_iters=max_iters,
        arrival_time=float(arrivals[i]), plan_config=cfg,
    ) for i in range(requests)]

    srv = RecoveryServer(slots=slots, round_iters=C_ROUND_ITERS, rho=C_RHO,
                         sigma=C_RHO)
    t0 = time.perf_counter()
    srv.warmup(reqs[0])
    print(f"  warmup (compile included): {secs(time.perf_counter() - t0)}")
    t0 = time.perf_counter()
    results = srv.serve(reqs)
    print(f"  serve: {secs(time.perf_counter() - t0)}; {srv.stats()['total']}",
          flush=True)
    check(len(results) == requests and len({r.request_id for r in results})
          == requests, f"{len(results)} results for {requests} requests")
    check(srv.stats()["total"]["recycled"] >= requests - slots,
          f"{srv.stats()['total']['recycled']} recycled admissions "
          f">= {requests - slots}")

    solo = jax.jit(functools.partial(solo_solve, config=cfg, rho=C_RHO))
    by_id = {r.request_id: r for r in reqs}
    req0 = reqs[0]
    compiled, tc = compile_timed(solo, op, req0.y, req0.tol, req0.min_iters,
                                 req0.max_iters)
    print(f"  solo solve_until compile: {secs(tc)}")
    check(has_kernel(compiled),
          "tail='pallas' solo program holds compiled kernels (tpu_custom_call)")
    t0 = time.perf_counter()
    worst, iters = 0.0, []
    for res in sorted(results, key=lambda r: r.request_id):
        req = by_id[res.request_id]
        x, used = compiled(op, req.y, req.tol, req.min_iters, req.max_iters)
        x = np.asarray(x)
        err = float(np.linalg.norm(res.x - x) / (np.linalg.norm(x) + 1e-12))
        worst = max(worst, err)
        iters.append((res.iterations, int(used)))
        check(res.converged, f"{res.request_id} (tol {req.tol}) converged "
              f"in {res.iterations} iterations")
    print(f"  solo references: {secs(time.perf_counter() - t0)}")
    check(all(a == b for a, b in iters),
          f"served == solo iteration counts {[a for a, _ in iters]}")
    check(worst <= SERVE_AGREEMENT,
          f"served vs solo worst rel {worst} <= {SERVE_AGREEMENT}")
    peak_bytes(jax.devices()[:1])


# ---------------------------------------------------------------------------
# --four: one comparison on a (1, 4) data x model mesh
# ---------------------------------------------------------------------------


def dist_solve(prob, *, mesh, config, iters: int, alpha: float, rho: float,
               seen: dict):
    from repro.core import solve
    from repro.ops import plan

    pl = plan(prob.op, mesh, config=config)
    seen["wire_dtype"] = pl.wire_dtype  # resolved while tracing
    x, _ = solve(prob, "cpadmm", iters=iters, record_every=iters, alpha=alpha,
                 rho=rho, sigma=rho, plan=pl)
    return x


def spread(tree, mesh):
    """Place every leaf whose last axis splits four ways over the model
    axis (the rest replicated), so no chip holds the whole problem."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    def put(a):
        p = mesh.shape["model"]
        spec = P(*(None,) * (a.ndim - 1), "model") if a.shape[-1] % p == 0 else P()
        return jax.device_put(a, NamedSharding(mesh, spec))

    return jax.tree.map(put, tree)


def phase_four(seed: int, n=F_N, iters=F_ITERS, devices=None):
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.ops import PlanConfig

    devices = jax.devices() if devices is None else devices
    check(len(devices) >= 4, f"{len(devices)} devices >= 4")
    mesh = Mesh(np.asarray(devices[:4]).reshape(1, 4), ("data", "model"))
    cfg = PlanConfig(rfft=True, overlap=1, wire_dtype="fp32", batch_axis="data")
    print(f"phase four: n={n}, B=1, CPADMM {iters} iterations on a (1, 4) "
          f"data x model mesh ({cfg.describe()}) vs the same solve on one chip",
          flush=True)
    build = jax.jit(build_paper_problem, static_argnums=(1, 2))
    prob, t = timed(build, jax.random.PRNGKey(seed), n, 1)
    print(f"  build (one jitted program on device 0): {secs(t)}")

    seen: dict = {}
    fn = jax.jit(functools.partial(dist_solve, mesh=mesh, config=cfg,
                                   iters=iters, alpha=F_ALPHA, rho=F_RHO,
                                   seen=seen))
    x_dist, t = timed(lambda p: fn(spread(p, mesh)), prob)
    print(f"  4-chip solve (placement and compile included): {secs(t)}",
          flush=True)
    check(seen.get("wire_dtype") == "fp32",
          f"plan kept wire_dtype={seen.get('wire_dtype')}")
    peak_bytes(devices[:4])

    local = jax.jit(functools.partial(paper_solve, tail="jnp", iters=iters,
                                      alpha=F_ALPHA, rho=F_RHO))
    x_local, t = timed(local, prob)
    print(f"  1-chip solve (compile included): {secs(t)}", flush=True)
    x_dist = jax.device_put(x_dist, devices[0])
    agree = float(rel(x_dist, x_local).max())
    check(agree <= DIST_AGREEMENT,
          f"rel(4-chip, 1-chip) {agree} <= {DIST_AGREEMENT}")
    err = float(rel(x_local, prob.x_true).max())
    print(f"  rel err vs x_true (1-chip): {err}")


def main(argv=None) -> int:
    global KIND
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four", action="store_true",
                    help="run only the four-chip comparison")
    args = ap.parse_args(argv)

    t_start = time.perf_counter()
    cache = configure_compile_cache()
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX reports platform {platform!r})",
              file=sys.stderr)
        return 2
    KIND = devices[0].device_kind
    print(f"device: platform={platform} kind={KIND} count={len(devices)} "
          f"(jax {jax.__version__})")
    print(f"compile cache: {cache}")
    shutil.rmtree(WORK_DIR, ignore_errors=True)
    os.makedirs(WORK_DIR)
    os.environ["REPRO_PLAN_CACHE"] = os.path.join(WORK_DIR, "plan_cache.json")

    if args.four:
        phase_four(args.seed)
    else:
        for phase in (phase_paper, phase_deblur, phase_serve):
            t0 = time.perf_counter()
            phase(args.seed)
            print(f"  phase total: {secs(time.perf_counter() - t0)}", flush=True)
    print(f"total: {secs(time.perf_counter() - t_start)}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": KIND, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

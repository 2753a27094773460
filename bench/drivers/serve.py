"""Served traffic: an open loop of recovery requests into a RecoveryServer.

Traffic parameters (``bench/traffic/<mix>.json``):

    slots, round_iters      the server's lanes and iterations per round
    rate_per_s              offered load, fixed in the file (not searched)
    mix                     request classes: name, tol, share
    min_iters, max_iters    every request's iteration budget
    lead_in_s               uncounted arrivals before the window, at the same
                            rate, so the window starts with full slots
    drain_limit_s           how long after the window results are awaited
    check_requests          results compared with the reference, drawn from
                            the seed, the one with most iterations always in

The arrivals are a stratified Poisson schedule, not independent draws:
every seed gets the same set of inter-arrival gaps (the midpoint quantiles
of the exponential distribution at the rate, scaled to fill the window)
and the same count of each request class, in an order drawn from the seed,
and its own frames: the same work in another order, so runs of different
seeds differ by the order alone.  Arrivals fall in [0, --seconds) on the server's clock;
each request's latency runs from its scheduled arrival to the harvest of
its result, so queueing counts.  Requests that arrived in the window are
drained after it and counted; ``attempted`` is their number and ``failed``
those not harvested converged.
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

import gen
import reference
from harness import Outcome, log


def schedule(seed: int, rate: float, span: float, mix, start: float = 0.0):
    """(arrival times, tolerance per request) for ``round(rate * span)``
    requests in [start, start + span)."""
    count = max(1, int(round(rate * span)))
    rng = np.random.default_rng(seed)
    gaps = -np.log1p(-(np.arange(count) + 0.5) / count) / rate
    gaps = rng.permutation(gaps)
    times = start + (np.cumsum(gaps) - gaps[0]) * (span / gaps.sum())
    shares = np.asarray([c["share"] for c in mix], float)
    counts = np.floor(count * shares / shares.sum()).astype(int)
    counts[0] += count - counts.sum()
    tols = rng.permutation(np.repeat([c["tol"] for c in mix], counts))
    return times, tols


class _Clock:
    """The server's clock: seconds since the window's scheduled start."""

    def __init__(self, zero: float):
        self.zero = zero

    def now(self) -> float:
        return time.perf_counter() - self.zero

    def advance_to(self, t: float) -> None:
        dt = t - self.now()
        if dt > 0:
            time.sleep(dt)


def _requests(ctx, op, ys, times, tols):
    from repro.serve import RecoveryRequest

    cfg, tr = ctx.config, ctx.traffic
    pc = ctx.deployment.plan_config(cfg)
    return [RecoveryRequest(request_id=f"r{i:05d}", op=op, y=ys[i], tol=float(tols[i]),
                            min_iters=tr["min_iters"], max_iters=tr["max_iters"],
                            arrival_time=float(times[i]), method=cfg["method"],
                            plan_config=pc)
            for i in range(len(times))]


def run(ctx) -> Outcome:
    from repro.serve import RecoveryServer

    cfg, tr, dep = ctx.config, ctx.traffic, ctx.deployment
    rate, seconds, lead = tr["rate_per_s"], ctx.seconds, tr["lead_in_s"]
    t_lead, tol_lead = (schedule(ctx.seed + 1, rate, lead, tr["mix"], -lead)
                        if lead > 0 else (np.zeros(0), np.zeros(0)))
    t_win, tol_win = schedule(ctx.seed, rate, seconds, tr["mix"])
    times = np.concatenate([t_lead, t_win])
    tols = np.concatenate([tol_lead, tol_win])
    count = len(times)

    def build(key):
        raw, y = dep.build(cfg, key, count)
        return raw, tuple(y)

    raw, ys = jax.block_until_ready(jax.jit(build)(gen.key_from_seed(ctx.seed)))
    op = jax.block_until_ready(jax.jit(functools.partial(dep.program_operator, cfg))(raw))
    reqs = _requests(ctx, op, ys, times, tols)
    window_ids = {r.request_id for r in reqs[len(t_lead):]}

    clock = _Clock(time.perf_counter())
    server = RecoveryServer(slots=tr["slots"], round_iters=tr["round_iters"],
                            alpha=cfg["alpha"], rho=cfg["rho"], sigma=cfg["sigma"],
                            clock=clock)
    server.warmup(reqs[0])
    clock.zero = time.perf_counter() + lead
    done: dict = {}
    nxt = 0

    def loop(until):
        nonlocal nxt
        while not until():
            now = clock.now()
            while nxt < count and reqs[nxt].arrival_time <= now:
                with ctx.span("bench.submit"):
                    server.submit(reqs[nxt])
                nxt += 1
            if server.pending or server.busy:
                with ctx.span("bench.step"):
                    for r in server.step():
                        done[r.request_id] = r
            elif nxt < count:
                with ctx.span("bench.wait"):
                    clock.advance_to(min(reqs[nxt].arrival_time, seconds))
            else:
                return

    loop(lambda: clock.now() >= 0.0)
    ctx.begin_window()
    before = dict(server.stats()["total"])
    loop(lambda: clock.now() >= seconds)
    after = dict(server.stats()["total"])
    backlog = server.pending + sum(e.slots - len(e.free_slots()) for e in server.engines.values())
    ctx.end_window()
    loop(lambda: window_ids <= done.keys() or clock.now() >= seconds + tr["drain_limit_s"])
    ctx.close_trace()
    memory = ctx.memory_peak()

    got = [done[i] for i in sorted(window_ids & done.keys())]
    lat = np.asarray([r.latency for r in got])
    missing = len(window_ids) - len(got)
    failed = missing + sum(not r.converged for r in got)
    window = {k: after[k] - before[k] for k in after}
    log(f"window requests {len(window_ids)}, harvested {len(got)}, "
        f"iterations {sorted(r.iterations for r in got)}")
    e2e = {"p90_latency_s": float(np.percentile(lat, 90))} if len(lat) else {}
    counters = {**{f"window_{k}": v for k, v in window.items()},
                "p50_latency_s": float(np.percentile(lat, 50)) if len(lat) else None,
                "slots": tr["slots"], "round_iters": tr["round_iters"],
                "requests": len(window_ids), "harvested": len(got),
                "finished_in_window_per_s":
                    sum(0.0 <= r.finish_time <= seconds for r in done.values()) / seconds,
                "backlog_end": backlog}
    del server, reqs, op

    checks = {"missing": float(missing)}
    if tr["check_requests"] and got:
        checks.update(_compare(ctx, raw, ys, tols, got))
    return Outcome(e2e=e2e, attempted=len(window_ids), failed=failed, counters=counters,
                   checks=checks, memory_peak_bytes=memory)


def _sample(seed: int, got, k: int):
    rng = np.random.default_rng(seed)
    longest = max(range(len(got)), key=lambda i: got[i].iterations)
    rest = [i for i in range(len(got)) if i != longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [got[longest]] + [got[rest[i]] for i in sorted(pick)]


def reference_until(ctx, lowp=False):
    cfg = ctx.config
    return jax.jit(functools.partial(
        reference.cpadmm_until, alpha=cfg["alpha"], rho=cfg["rho"], sigma=cfg["sigma"],
        tau=cfg["tau"], lowp=lowp))


def _compare(ctx, raw, ys, tols, got):
    """The reference over a sample of the window's results: worst relative
    gap of x at the served iteration count, worst gap between the served
    and the reference's stopping iteration, and converged flags that differ.
    Under ``ctx.control`` the lower-precision reference answers instead of
    the server: its iterate, stopping iteration and flag."""
    tr = ctx.traffic
    sample = _sample(ctx.seed, got, tr["check_requests"])
    idx = [int(r.request_id[1:]) for r in sample]
    y = jnp.stack([ys[i] for i in idx])
    tol = jnp.asarray(tols[idx], jnp.float32)
    at = jnp.asarray([r.iterations for r in sample], jnp.int32)
    ref_op = ctx.deployment.reference_operator(ctx.config, raw)
    x = jnp.stack([jnp.asarray(r.x) for r in sample])
    served_conv = np.asarray([r.converged for r in sample])
    t0 = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        if ctx.control:  # run to its own stop, as a server would
            full = jnp.full_like(at, tr["max_iters"])
            _, at, served_conv, x = reference_until(ctx, lowp=True)(
                ref_op, y, tol, tr["min_iters"], tr["max_iters"], full)
            served_conv = np.asarray(served_conv)
        z, stop, conv, _ = reference_until(ctx)(ref_op, y, tol, tr["min_iters"],
                                                tr["max_iters"], at)
    gap = float(jnp.max(reference.rel_gap(x, z)))
    at, stop, conv = np.asarray(at), np.asarray(stop), np.asarray(conv)
    log(f"reference over {len(sample)} requests: {time.perf_counter() - t0} s; "
        f"served iterations {at.tolist()}, reference {stop.tolist()}")
    return {"rel_gap": gap,
            "iter_gap": float(np.max(np.abs(stop - at))),
            "flag_mismatch": float(np.sum(conv != served_conv))}


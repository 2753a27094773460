"""The continuous-batching engine: one bucket's slots, recycled mid-run.

A :class:`BatchEngine` owns ``slots`` lanes of one batched solver — every
lane shares the bucket's operator, method, and execution plan, but carries
its *own* convergence contract (per-slot ``tol`` / ``min_iters`` /
``max_iters`` arrays, the contract :func:`repro.core.solvers.solve_until`
grew for exactly this).  The engine advances all lanes together in jitted
*rounds* of ``round_iters`` masked iterations, then hands control back to
the host scheduler, which

  1. **harvests** lanes that went inactive (converged, budget-exhausted, or
     deadline-expired) — their iterate rows become results, and
  2. **recycles** the freed lanes: a queued request is admitted *mid-run*
     with the slot's solver state, ``delta``, and iteration age re-armed
     (:func:`repro.core.solvers.rearm_slots`), so the batch never drains to
     its stragglers — the LLM-continuous-batching mechanism applied to
     compressed-signal recovery.

Because freezing and re-arming are pure per-slot where-selects, a recycled
lane computes exactly what a solo :func:`solve_until` run would (pinned to
1e-5 in tests/test_serve.py).  One XLA program is compiled per engine; y
and every per-slot array are traced arguments, so admission never re-jits.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.solvers import (
    RecoveryProblem,
    make_stepper,
    rearm_slots,
    until_active,
    until_init,
    until_step,
)

from .request import RecoveryRequest, RecoveryResult


def make_round_fn(op, plan, method, batch, round_iters, **solver_kw):
    """The engine's jitted round: up to ``round_iters`` masked iterations of
    every active lane, then the lanes' iterates.

    The operator is closed over; the measurement rows ``y``, the carry and
    the per-slot contracts are traced arguments, so admitting a new row
    never re-compiles.  The carry ``u`` is donated: the round writes the
    next carry into its buffers.  ``solver_kw`` are ``make_stepper``'s
    ``alpha`` / ``rho`` / ``sigma``.
    """

    @functools.partial(jax.jit, donate_argnums=1)
    def round_fn(y, u, tol, mn, mx):
        stepper = make_stepper(RecoveryProblem(op=op, y=y), method, plan=plan,
                               **solver_kw)

        def cond(c):
            u, k = c
            return jnp.logical_and(
                k < round_iters, jnp.any(until_active(u, tol, mn, mx))
            )

        def body(c):
            u, k = c
            return until_step(stepper, u, tol, mn, mx, batch), k + 1

        (u, _) = jax.lax.while_loop(cond, body, (u, jnp.int32(0)))
        return u, stepper.extract(u.state)

    return round_fn


class BatchEngine:
    """``slots`` lanes of one batched solver, recycled round by round.

    ``stats`` counts the engine's work on the host, where it happens, with
    no device work or sync of its own: admissions, recycled admissions,
    rounds, slot iterations stepped (from the ``age`` vector each harvest
    reads), results harvested, seconds requests queued before admission,
    and the bytes the harvest read from the device.  Each of
    :meth:`admit`, :meth:`run_round` and :meth:`harvest` is a profiler span
    (``serve.admit``, ``serve.round``, ``serve.harvest``), recorded only
    while a ``jax.profiler`` trace runs.
    """

    def __init__(
        self,
        op: Any,
        plan: Any,
        method: str = "cpadmm",
        slots: int = 8,
        round_iters: int = 32,
        alpha: float = 1e-4,
        rho: float = 0.1,
        sigma: float = 0.1,
        bucket: str = "",
    ):
        self.op = op
        self.plan = plan
        self.method = method
        self.slots = int(slots)
        self.round_iters = int(round_iters)
        self.alpha, self.rho, self.sigma = alpha, rho, sigma
        self.bucket = bucket

        distributed = plan is not None and getattr(plan, "is_distributed", False)
        # the drivers' measurement convention: length-m rows locally,
        # scattered full-length rows (P^T y) on a mesh — requests arrive as
        # length-m and are scattered at admission when needed
        self._y_len = op.n if distributed else op.m
        self._scatter = distributed
        dtype = jnp.asarray(getattr(getattr(op, "circ", op), "col")).dtype
        self._y = jnp.zeros((self.slots, self._y_len), dtype)

        # per-slot convergence contracts; empty slots are parked with
        # max_iters = 0, which until_active treats as never-active
        self._tol = jnp.full((self.slots,), jnp.inf, dtype)
        self._min = jnp.zeros((self.slots,), jnp.int32)
        self._max = jnp.zeros((self.slots,), jnp.int32)

        # host-side slot metadata
        self._requests: List[Optional[RecoveryRequest]] = [None] * self.slots
        self._admitted_at: List[Optional[float]] = [None] * self.slots
        self._slot_used = [False] * self.slots
        # each lane's age as of the last harvest (0 once re-armed): the
        # baseline slot_iters is counted against
        self._age = np.zeros((self.slots,), np.int64)

        self.stats: Dict[str, float] = {
            "admitted": 0,  # requests that reached a slot
            "recycled": 0,  # admissions into a lane freed mid-run
            "rounds": 0,  # jitted round launches
            "slot_iters": 0,  # sum of per-slot iterations actually stepped
            "harvested": 0,  # results returned
            "queue_wait_s": 0.0,  # sum of admission time - arrival time
            "d2h_bytes": 0,  # bytes harvest read from the device
        }

        solver_kw = dict(alpha=alpha, rho=rho, sigma=sigma)
        # the init carry: solver-state zeros + age 0 + delta inf — both the
        # engine's starting point and the value re-armed into recycled slots
        # (solver inits are y-independent, so one init serves every request)
        stepper0 = make_stepper(RecoveryProblem(op=op, y=self._y), method,
                                plan=plan, **solver_kw)
        self._u_init, self._batch = until_init(stepper0)
        # the live carry is donated to each re-arm and round, so it owns
        # buffers of its own, apart from the init carry and from each other
        self._u = jax.tree.map(jnp.copy, self._u_init)
        self._x = stepper0.extract(self._u_init.state)  # (slots, n) last extract

        batch = self._batch
        self._round_fn = make_round_fn(op, plan, method, batch,
                                       self.round_iters, **solver_kw)

        @functools.partial(jax.jit, donate_argnums=0)
        def rearm_fn(u, admit):
            return rearm_slots(u, self._u_init, admit, batch)

        self._rearm_fn = rearm_fn

    def lower_round(self):
        """The round program lowered at the engine's current arguments: the
        same avals and shardings a round runs with, so compiling it finds
        the compilation cache's entry.  Nothing runs."""
        return self._round_fn.lower(
            self._y, self._u, self._tol, self._min, self._max
        )

    # -- occupancy ---------------------------------------------------------
    @property
    def busy(self) -> bool:
        return any(r is not None for r in self._requests)

    def free_slots(self) -> List[int]:
        return [i for i, r in enumerate(self._requests) if r is None]

    # -- admission ---------------------------------------------------------
    def admit(self, slot: int, req: RecoveryRequest, now: float) -> None:
        """Place ``req`` into a free slot, re-arming that lane's state."""
        if self._requests[slot] is not None:
            raise RuntimeError(f"slot {slot} is occupied")
        with TraceAnnotation("serve.admit", request_id=req.request_id):
            y = jnp.asarray(req.y, self._y.dtype)
            if self._scatter and y.shape[-1] != self._y_len:
                y = self.op.project_back(y)
            if y.shape[-1] != self._y_len:
                raise ValueError(
                    f"request {req.request_id!r}: measurement length "
                    f"{y.shape[-1]} does not fit this bucket's operator "
                    f"(expects {self._y_len})"
                )
            self._y = self._y.at[slot].set(y)
            self._tol = self._tol.at[slot].set(req.tol)
            self._min = self._min.at[slot].set(req.min_iters)
            self._max = self._max.at[slot].set(req.max_iters)
            admit_mask = jnp.zeros((self.slots,), bool).at[slot].set(True)
            self._u = self._rearm_fn(self._u, admit_mask)
        self._age[slot] = 0
        self._requests[slot] = req
        self._admitted_at[slot] = now
        self.stats["admitted"] += 1
        self.stats["queue_wait_s"] += now - req.arrival_time
        if self._slot_used[slot]:
            self.stats["recycled"] += 1
        self._slot_used[slot] = True

    def park(self, slot: int) -> None:
        """Return a harvested lane to the never-active parked state."""
        self._requests[slot] = None
        self._admitted_at[slot] = None
        self._max = self._max.at[slot].set(0)
        self._tol = self._tol.at[slot].set(jnp.inf)

    # -- the round ---------------------------------------------------------
    def run_round(self) -> None:
        """Advance every active lane up to ``round_iters`` masked iterations."""
        if not self.busy:
            return
        with TraceAnnotation("serve.round"):
            self._u, self._x = self._round_fn(
                self._y, self._u, self._tol, self._min, self._max
            )
            with TraceAnnotation("serve.round.wait"):
                jax.block_until_ready(self._x)
        self.stats["rounds"] += 1

    # -- harvest -----------------------------------------------------------
    def _read(self, *arrays) -> list:
        """``device_get`` of each array, counted in ``d2h_bytes``."""
        host = [jax.device_get(a) for a in arrays]
        self.stats["d2h_bytes"] += sum(h.nbytes for h in host)
        return host

    def harvest(self, now: float) -> List[RecoveryResult]:
        """Collect finished lanes: converged / budget-exhausted lanes, plus
        any whose deadline has passed (flagged partial results)."""
        if not self.busy:
            return []
        with TraceAnnotation("serve.harvest"):
            with TraceAnnotation("serve.harvest.poll"):
                age, delta, tol, mn, mx = self._read(
                    self._u.age, self._u.delta, self._tol, self._min, self._max
                )
            self.stats["slot_iters"] += int(np.sum(age - self._age))
            self._age = np.array(age, np.int64)
            done = []
            for i, req in enumerate(self._requests):
                if req is None:
                    continue
                inactive = age[i] >= mx[i] or (age[i] >= mn[i] and delta[i] <= tol[i])
                expired = req.deadline is not None and now >= req.deadline
                if inactive or expired:
                    done.append((i, req, expired))
            if not done:
                return []
            ids = ",".join(req.request_id for _, req, _ in done)
            with TraceAnnotation("serve.harvest.fetch", lanes=len(done),
                                 request_id=ids):
                (x_host,) = self._read(self._x)
            out: List[RecoveryResult] = []
            for i, req, expired in done:
                converged = bool(delta[i] <= tol[i] and age[i] >= mn[i])
                out.append(RecoveryResult(
                    request_id=req.request_id,
                    x=x_host[i],
                    iterations=int(age[i]),
                    delta=float(delta[i]),
                    converged=converged,
                    deadline_expired=bool(expired and not converged),
                    arrival_time=req.arrival_time,
                    admitted_time=self._admitted_at[i],
                    finish_time=now,
                    bucket=self.bucket,
                ))
                self.park(i)
        self.stats["harvested"] += len(out)
        return out

"""Unified recovery driver for the paper's solver family.

Methods
-------
    'ista'    Alg. 1 on any operator (dense op => the paper's PISTA baseline,
              circulant op => CPISTA: same algorithm, structured matvecs)
    'fista'   beyond-paper accelerated variant (same cost/iteration)
    'admm'    Alg. 2 on a dense operator (PADMM baseline; O(n^3) setup)
    'cpadmm'  Alg. 3 on a PartialCirculant (FFT setup + structured iterations)

Drivers
-------
    solve()              fixed iteration count, jit-scanned, metric traces
    solve_until()        while-loop with relative-change tolerance
    solve_checkpointed() host-chunked loop with checkpoint/restart callbacks —
                         the fault-tolerance path for very long recoveries
                         (paper Sec. 7 runs 3 h on a desktop GPU; at that
                         horizon restartability is a production requirement)

Every driver accepts a leading batch axis on ``y`` / ``x_true`` (B signals
sensed through one shared operator — the paper's off-line many-recoveries
workload): states, traces, and MSEs broadcast per signal, and
``solve_until`` tracks convergence per signal, freezing early finishers
instead of stalling the batch.  Batch-of-1 equals the unbatched run
(tests/test_batched_recovery.py).

Backends: every driver takes ``plan=`` (repro.ops.plan).  With no plan (or
a local ``plan(op)``) the steppers run the operator's own matvecs on one
device; with a distributed plan the same methods lower to the sharded
four-step transforms of repro.dist — these drivers are the only drivers,
so tolerance stopping, per-signal freezing, metric traces, and
checkpoint/restart work identically on a mesh (tests/test_plan.py,
tests/dist_progs/ista_prog.py).  A local plan's ``tail='pallas'`` swaps the
CPADMM step onto the fused kernel substrate (core.kernel_backend).

Recovery success follows the paper: MSE = ||x* - x||^2 / n <= 1e-4 (Sec. 6).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.ops import prox as prox_mod

from . import admm as admm_mod
from . import ista as ista_mod
from .circulant import DenseOperator, PartialCirculant

Array = jax.Array

PAPER_TARGET_MSE = 1e-4  # paper Sec. 6 recovery threshold


class RecoveryProblem(NamedTuple):
    op: Any  # matvec/rmatvec-capable operator
    y: Array  # (..., m) measurements
    x_true: Optional[Array] = None  # (..., n) ground truth (metrics only)


class Trace(NamedTuple):
    objective: Array  # (T, ...) LASSO objective per recorded step
    mse: Array  # (T, ...) MSE vs x_true (nan if no truth)
    nnz: Array  # (T, ...) support size of the iterate


def _metrics(problem: RecoveryProblem, x: Array, alpha) -> Tuple[Array, Array, Array]:
    obj = ista_mod.lasso_objective(problem.op, problem.y, x, alpha)
    if problem.x_true is not None:
        d = problem.x_true - x
        mse = jnp.mean(d * d, axis=-1)
    else:
        mse = jnp.full(obj.shape, jnp.nan, x.dtype)
    nnz = jnp.sum((jnp.abs(x) > 0).astype(jnp.int32), axis=-1)
    return obj, mse, nnz


def _metric_view(problem: RecoveryProblem, plan) -> RecoveryProblem:
    """The problem the metric traces are computed against.

    On a distributed plan the objective runs through the plan's mask-form
    operator (``||P^T y - diag(mask) C x||^2`` equals the m-subset
    objective, since the off-omega rows of both terms are zero) so metric
    matvecs stay sharded instead of replicating a full-size local FFT per
    recorded step.
    """
    if plan is None or not getattr(plan, "is_distributed", False):
        return problem
    return RecoveryProblem(
        op=plan.operator,
        y=plan._scattered_measurements(problem),
        x_true=problem.x_true,
    )


@dataclasses.dataclass(frozen=True)
class Stepper:
    """A (init, step, extract) triple hiding per-method state shapes."""

    init: Callable[[], Any]
    step: Callable[[Any], Any]
    extract: Callable[[Any], Array]  # state -> current x


VALID_METHODS = ("ista", "fista", "cpista", "admm", "padmm", "cpadmm")


def make_stepper(
    problem: RecoveryProblem,
    method: str,
    alpha: float = 1e-4,
    rho: float = 0.1,
    sigma: float = 0.1,
    tau: Optional[float] = None,
    plan=None,
    prox=None,
) -> Stepper:
    """Lower (problem, method) to a Stepper on the plan's backend.

    ``plan=None`` (or a local plan) runs the operator's own matvecs; a
    distributed plan (repro.ops.plan with a mesh) lowers the same method to
    the sharded four-step transforms — the stepper contract (init / step /
    extract-flat-x) is identical, which is what lets every driver below run
    unchanged on both backends.

    ``prox=`` swaps the prior (repro.ops.prox); None defaults to the plan's
    ``prox`` and then to the paper's identity-basis soft threshold, which
    keeps the fused Pallas tails eligible.  A non-l1 prox composes the
    z-update outside the fused kernels, so a ``tail='pallas'`` plan refuses
    it (``repro.ops.prox.check_tail``).
    """
    if prox is None and plan is not None:
        prox = getattr(plan, "prox", None)
    tail = getattr(plan, "tail", "jnp") if plan is not None else "jnp"
    prox_mod.check_tail(tail, prox)
    if plan is not None and getattr(plan, "is_distributed", False):
        return plan.build_stepper(
            problem, method, alpha=alpha, rho=rho, sigma=sigma, tau=tau, prox=prox
        )
    op, y = problem.op, problem.y
    if method in ("ista", "fista", "cpista"):
        tau_v = (
            jnp.asarray(tau, y.dtype) if tau is not None else ista_mod.default_tau(op)
        )
        p = ista_mod.IstaParams(alpha=jnp.asarray(alpha, y.dtype), tau=tau_v)
        step_fn = ista_mod.fista_step if method == "fista" else ista_mod.ista_step
        return Stepper(
            init=lambda: ista_mod.ista_init(op, y),
            step=lambda s: step_fn(op, y, s, p, prox=prox),
            extract=lambda s: s.x,
        )
    if method in ("admm", "padmm"):
        if not isinstance(op, DenseOperator):
            raise TypeError("dense ADMM needs a DenseOperator; use 'cpadmm'")
        const = admm_mod.dense_admm_setup(op, y, rho)
        return Stepper(
            init=lambda: admm_mod.dense_admm_init(op, y),
            step=lambda s: admm_mod.dense_admm_step(const, s, alpha, rho, prox=prox),
            extract=lambda s: s.z,  # z is the sparse iterate
        )
    if method == "cpadmm":
        if not isinstance(op, PartialCirculant):
            raise TypeError("cpadmm needs a PartialCirculant operator")
        p = admm_mod.CpadmmParams(
            alpha=jnp.asarray(alpha, y.dtype),
            rho=jnp.asarray(rho, y.dtype),
            sigma=jnp.asarray(sigma, y.dtype),
            tau1=jnp.asarray(1.0 if tau is None else tau, y.dtype),
            tau2=jnp.asarray(1.0 if tau is None else tau, y.dtype),
        )
        const = admm_mod.cpadmm_setup(op, y, p)
        if tail == "pallas":
            # plan attribute tail='pallas' on the local backend: the fused
            # kernels/cpadmm_tail substrate (core.kernel_backend); check_tail
            # above has already refused non-l1 priors for it.
            from repro.kernels.cpadmm_tail.ops import interpret_default

            from .kernel_backend import cpadmm_step_pallas

            interpret = interpret_default()
            step = lambda s: cpadmm_step_pallas(op, const, s, p, interpret=interpret)
        else:
            step = lambda s: admm_mod.cpadmm_step(op, const, s, p, prox=prox)
        return Stepper(
            init=lambda: admm_mod.cpadmm_init(op, y),
            step=step,
            extract=lambda s: s.z,
        )
    raise ValueError(
        f"unknown method {method!r}; valid methods: {', '.join(VALID_METHODS)}"
    )


def solve(
    problem: RecoveryProblem,
    method: str = "cpadmm",
    iters: int = 200,
    alpha: float = 1e-4,
    record_every: Optional[int] = None,
    plan=None,
    **kw,
) -> Tuple[Array, Trace]:
    """Run a fixed number of iterations under jit; record metric traces.

    ``plan=`` selects the execution backend (repro.ops.plan).  Each metric
    record costs one operator application, so ``record_every`` defaults to
    1 locally but to ``iters`` (a single trace point) on a distributed
    plan — a per-iteration trace there would add two transpose-collectives
    per iteration on top of the fused step's two; pass ``record_every``
    explicitly to trace a distributed run more often.
    """
    if record_every is None:
        distributed = plan is not None and getattr(plan, "is_distributed", False)
        record_every = iters if distributed else 1
    stepper = make_stepper(problem, method, alpha=alpha, plan=plan, **kw)
    metric_problem = _metric_view(problem, plan)
    inner = max(1, record_every)
    outer = max(1, iters // inner)

    def scan_body(state, _):
        state, _ = jax.lax.scan(
            lambda s, _: (stepper.step(s), None), state, None, length=inner
        )
        x = stepper.extract(state)
        return state, _metrics(metric_problem, x, alpha)

    state, (obj, mse, nnz) = jax.lax.scan(
        scan_body, stepper.init(), None, length=outer
    )
    return stepper.extract(state), Trace(objective=obj, mse=mse, nnz=nnz)


def _freeze_converged(new_state, old_state, active: Array, batch: Tuple[int, ...]):
    """Keep stepping active signals, freeze converged ones.

    ``active`` has the batch shape; every state leaf carrying the batch as
    leading dims is masked per signal (including the per-signal FISTA
    momentum, which is batched so a frozen — or later recycled — slot's
    momentum schedule matches a solo run).  Leaves without the batch prefix
    advance globally — harmless, since frozen signals' arrays no longer
    consume them.
    """

    def sel(new_leaf, old_leaf):
        if batch and new_leaf.shape[: len(batch)] == batch:
            m = active.reshape(batch + (1,) * (new_leaf.ndim - len(batch)))
            return jnp.where(m, new_leaf, old_leaf)
        return new_leaf

    with jax.named_scope("until.freeze"):
        return jax.tree.map(sel, new_state, old_state)


class UntilState(NamedTuple):
    """The tolerance-driven loop's carry, per slot.

    ``age`` counts iterations *since admission* (== iterations used once a
    slot converges) and ``delta`` is the last relative iterate change.  Both
    have the batch shape, which is what makes a slot re-armable mid-run:
    admitting a new signal into a converged slot resets that slot's state
    leaves, age, and delta (:func:`rearm_slots`) without disturbing its
    neighbours — the continuous-batching mechanism ``repro.serve`` builds
    on.  Keeping only a global iteration counter (the pre-serve design)
    would make a recycled slot inherit its predecessor's sub-``tol`` delta
    and iteration count, freezing it instantly before ``min_iters`` could
    apply.
    """

    state: Any  # solver state (leaves carry the batch prefix)
    age: Array  # (batch,) int32 — iterations since (re-)admission
    delta: Array  # (batch,) last relative iterate change (inf before a step)


def until_init(stepper: Stepper) -> Tuple[UntilState, Tuple[int, ...]]:
    """Fresh loop carry for a stepper; returns (carry, batch_shape)."""
    s0 = stepper.init()
    x0 = stepper.extract(s0)
    batch = x0.shape[:-1]
    return (
        UntilState(
            state=s0,
            age=jnp.zeros(batch, jnp.int32),
            delta=jnp.full(batch, jnp.inf, x0.dtype),
        ),
        batch,
    )


def until_active(u: UntilState, tol, min_iters, max_iters) -> Array:
    """Per-slot liveness: still inside the budget AND (young OR moving).

    ``tol`` / ``min_iters`` / ``max_iters`` may be scalars or per-slot
    arrays broadcastable to the batch shape — per-slot budgets are what let
    a serving batch mix requests with heterogeneous tolerances (and park
    empty slots with ``max_iters = 0``).

    ``min_iters`` guards against the thresholded iterate being frozen at 0
    during the first iterations (the relative change would be spuriously 0).
    """
    return jnp.logical_and(
        u.age < max_iters,
        jnp.logical_or(u.age < min_iters, u.delta > tol),
    )


def until_step(
    stepper: Stepper,
    u: UntilState,
    tol,
    min_iters,
    max_iters,
    batch: Tuple[int, ...],
) -> UntilState:
    """One masked iteration: step active slots, freeze the rest, update each
    active slot's age and relative change.  Frozen slots keep their last
    delta (the reporting value; a recycled slot gets a fresh inf via
    :func:`rearm_slots`, never this stale one).  The liveness mask, the
    norms and the selects are the named scope ``until.test``."""
    with jax.named_scope("until.test"):
        active = until_active(u, tol, min_iters, max_iters)
    new = _freeze_converged(stepper.step(u.state), u.state, active, batch)
    with jax.named_scope("until.test"):
        x_old = stepper.extract(u.state)
        x_new = stepper.extract(new)
        num = jnp.linalg.norm(x_new - x_old, axis=-1)
        den = jnp.linalg.norm(x_old, axis=-1) + 1e-12
        return UntilState(
            state=new,
            age=jnp.where(active, u.age + 1, u.age),
            delta=jnp.where(active, num / den, u.delta),
        )


def rearm_slots(
    u: UntilState, init: UntilState, admit: Array, batch: Tuple[int, ...]
) -> UntilState:
    """Admit new work into slots: where ``admit`` (batch-shaped bool), take
    the *init* carry — state leaves re-zeroed, age 0, delta inf — so the
    admitted signal runs exactly as it would alone; everywhere else the
    carry is untouched.  jit-friendly (pure where-select)."""
    return UntilState(
        state=_freeze_converged(init.state, u.state, admit, batch),
        age=jnp.where(admit, init.age, u.age),
        delta=jnp.where(admit, init.delta, u.delta),
    )


def solve_until(
    problem: RecoveryProblem,
    method: str = "cpadmm",
    tol=1e-7,
    max_iters=5000,
    min_iters=50,
    alpha: float = 1e-4,
    plan=None,
    **kw,
) -> Tuple[Array, Array]:
    """Iterate until relative iterate change < tol (or max_iters); returns
    (x, iterations_used).  Pure lax.while_loop — jit/pjit friendly.

    Batched: with measurements ``y`` of shape (..., m) the convergence test
    is per signal.  Signals whose relative change drops below ``tol``
    *freeze* (their state stops updating) while the rest keep iterating, so
    one early-converging signal neither stalls the batch nor keeps burning
    flops; the loop exits when every signal has converged.
    ``iterations_used`` then has the batch shape (scalar when unbatched) and
    matches what each signal would have used in a solo run.

    ``tol`` / ``min_iters`` / ``max_iters`` may each be per-signal arrays
    (broadcastable to the batch shape) — heterogeneous convergence budgets
    in one batch, the contract the serving dispatcher (``repro.serve``)
    leans on.  The loop body itself is exposed as
    :func:`until_init` / :func:`until_step` / :func:`rearm_slots` so a host
    scheduler can run it round-by-round and admit new signals into
    converged slots mid-run (continuous batching).

    ``plan=`` selects the execution backend: a distributed plan gives
    tolerance-stopped *distributed* recovery (the convergence test runs on
    the flat extract, so the per-signal freeze semantics are identical).
    """
    stepper = make_stepper(problem, method, alpha=alpha, plan=plan, **kw)
    u0, batch = until_init(stepper)

    def cond(u):
        return jnp.any(until_active(u, tol, min_iters, max_iters))

    def body(u):
        return until_step(stepper, u, tol, min_iters, max_iters, batch)

    u = jax.lax.while_loop(cond, body, u0)
    return stepper.extract(u.state), u.age


def solve_checkpointed(
    problem: RecoveryProblem,
    method: str = "cpadmm",
    iters: int = 1000,
    chunk: int = 100,
    alpha: float = 1e-4,
    save_cb: Optional[Callable[[int, Any], None]] = None,
    restore: Optional[Tuple[int, Any]] = None,
    plan=None,
    **kw,
) -> Tuple[Array, Array]:
    """Host-chunked driver: jit-run ``chunk`` iterations at a time, invoking
    ``save_cb(step, state)`` between chunks.  ``restore=(step, state)``
    resumes an interrupted recovery — see repro.ckpt.solver_checkpoint.

    With a distributed ``plan=`` the saved state leaves are the sharded
    (n1, n2)-layout iterates — the fault-tolerance path for very long
    *distributed* recoveries (paper Sec. 7's three-hour horizon)."""
    stepper = make_stepper(problem, method, alpha=alpha, plan=plan, **kw)

    @jax.jit
    def run_chunk(state):
        def body(s, _):
            return stepper.step(s), None

        state, _ = jax.lax.scan(body, state, None, length=chunk)
        return state

    start, state = (0, stepper.init()) if restore is None else restore
    step = start
    while step < iters:
        state = run_chunk(state)
        step += chunk
        if save_cb is not None:
            save_cb(step, state)
    x = stepper.extract(state)
    _, mse, _ = _metrics(_metric_view(problem, plan), x, alpha)
    return x, mse

"""Execution plans: lower any RecoveryOperator to a solver backend.

``plan(op)`` is the identity lowering — the operator's own matvecs run on
one device, bit-exactly (tests/test_plan.py pins this).  ``plan(op, mesh)``
lowers the same operator to the sharded four-step transforms of
:mod:`repro.dist.fft`: matvecs become shard_mapped FFT applications (two
transpose-collectives each), and the CPADMM inner inverse stays a pointwise
spectral reciprocal on the column-sharded spectrum block.  Either way the
result is consumed by the *same* drivers — ``repro.core.solvers``'s
``solve`` / ``solve_until`` / ``solve_checkpointed`` take ``plan=`` and run
every method (ista / fista / cpadmm) on every backend, so tolerance
stopping, metric traces, per-signal convergence freezing, and
checkpoint/restart come for free on a mesh.

Distributed measurement convention
----------------------------------
On a mesh the m-subset gather/scatter of ``P`` would be a cross-shard
permutation, so the plan works in the *mask form* of the partial circulant:
``M = diag(mask) C`` with measurements scattered full-length
(``y_full = P^T y``).  The two forms produce identical solver iterates —
``M^T M = A^T A`` and ``M^T y_full = A^T y`` — and the drivers accept either
``problem.y`` of length m (scattered here via ``op.project_back``) or an
already-scattered length-n vector.

Plan attributes = backend knobs
-------------------------------
    rfft        half-spectrum transforms (half the FFT flops / wire bytes)
    overlap=K   chunked transpose-collectives overlapped with the local FFT
    tail        'jnp' or 'pallas' — the CPADMM elementwise-tail substrate
                (the fused kernels/cpadmm_tail VMEM pass); honored by the
                local backend too via core.kernel_backend
    fused       frequency-domain CPADMM x-update (2 all-to-alls/iter vs 6)
    batch_axis  mesh axis a leading batch of signals is sharded over
    wire_dtype  'fp32' (default) / 'bf16' / 'fp16' — the transpose
                all-to-all payload precision (repro.dist.fft wire packing);
                ``plan`` guards demoted wires with a one-matvec precision
                probe and falls back to fp32 past :data:`WIRE_ERROR_BOUND`
    hier_axes   (H, D) — run every transpose as the two-stage hierarchical
                exchange over the mesh's (host, device) axis pair
                (repro.dist.fft module docstring): intra-host all-to-all,
                local reshuffle, then inter-host hops carrying only the
                (H-1)/H cross-boundary payload.  None (default) keeps the
                flat exchange; a tuple ``axis_name=(host, device)`` with
                ``hier_axes=None`` is the flat layout *on* a hierarchical
                mesh (one monolithic all-to-all over both tiers)
    inter_wire_dtype  wire precision of only the inter-host (DCN) hops of
                the hierarchical exchange; guarded together with wire_dtype

All knobs live in one frozen, hashable :class:`PlanConfig` (also carrying
the four-step ``n1 x n2`` factorization and the mesh ``axis_name``): every
plan entry point — ``plan``, ``plan_from_parts``,
``launch.recover.build_plan``, ``core.deblur.build_deblur_plan`` — accepts
``config=PlanConfig(...)``, with the individual keyword arguments kept as a
thin compat path that constructs the same ``PlanConfig``
(:func:`resolve_plan_config` is the single validation site).  The config is
also the tuner's unit of currency: ``plan(op, mesh, tune=True)`` asks
:mod:`repro.ops.tune` to pick the config by cost model (see that module),
and the JSON tune cache stores winning configs verbatim.

All knobs are numerically pinned to their defaults
(tests/test_dist_equiv.py, tests/test_plan.py).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
import warnings
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.dist.compat import shard_map
from repro.dist.fft import (
    DEVICE_AXIS,
    HOST_AXIS,
    MODEL_AXIS,
    WIRE_DTYPES,
    col_spec,
    layout_2d,
    matvec_local,
    rmatvec_local,
    row_spec,
    unlayout_2d,
)
from repro.dist.recovery import (
    DistCpadmmParams,
    DistCpadmmState,
    dist_cpadmm_core,
    dist_cpadmm_step,
    dist_cpadmm_step_fused,
)

from . import prox as prox_mod
from . import spectral

Array = jax.Array

_ISTA_METHODS = ("ista", "fista", "cpista")

# wire-precision guard: plan(..) with wire_dtype != 'fp32' probes one matvec
# against the fp32-wire plan and falls back (RuntimeWarning) when the
# relative error exceeds this bound.  Overridable for experiments via the
# REPRO_WIRE_ERROR_BOUND env var; the documented default tolerates bf16's
# ~3 decimal digits across the two transposes of a matvec with margin.
WIRE_ERROR_BOUND = float(os.environ.get("REPRO_WIRE_ERROR_BOUND", "1e-2"))


def _factorize(n: int, n1: Optional[int], n2: Optional[int], p: int, rfft: bool):
    """Pick/validate the four-step n = n1 x n2 split for a p-device axis.

    Constraints come from the transpose-collectives: rows (n1) must split
    evenly over the axis, and so must the spectrum columns unless the rfft
    path pads them (``spectral.padded_rfft_len``).
    """
    if n1 is not None and n2 is None:
        n2 = n // n1
    if n1 is None and n2 is not None:
        n1 = n // n2
    if n1 is None:
        for cand in range(math.isqrt(n), 0, -1):
            if n % cand:
                continue
            a, b = cand, n // cand
            if a % p == 0 and (rfft or b % p == 0):
                n1, n2 = a, b
                break
        else:
            raise ValueError(
                f"no n1 x n2 = {n} factorization shards over {p} devices; "
                f"pass n1/n2 explicitly"
            )
    if n1 * n2 != n:
        raise ValueError(f"n1 * n2 = {n1}*{n2} != n = {n}")
    if n1 % p:
        raise ValueError(f"n1 = {n1} must be divisible by the mesh axis size {p}")
    if not rfft and n2 % p:
        raise ValueError(
            f"n2 = {n2} must be divisible by the mesh axis size {p} "
            f"(or use rfft=True, which pads the kept columns)"
        )
    return n1, n2


@dataclasses.dataclass(frozen=True)
class PlanConfig:
    """Every backend knob of an execution plan, in one frozen hashable value.

    The fields are exactly the plan attributes documented in the module
    docstring plus the four-step factorization (``n1 x n2``) and the mesh
    axis the within-signal transforms shard over.  ``n1``/``n2`` left as
    ``None`` means "auto-factorize near sqrt(n)" (``plan``) — they must be
    concrete for ``plan_from_parts``, which has no operator to read ``n``
    from.

    A ``PlanConfig`` is hashable and JSON round-trippable (``to_dict`` /
    ``from_dict``), which is what lets the autotuner (:mod:`repro.ops.tune`)
    use it both as the candidate-space element and as the cached winner.
    """

    rfft: bool = False
    overlap: int = 1
    tail: str = "jnp"
    fused: bool = True
    batch_axis: Any = None
    n1: Optional[int] = None
    n2: Optional[int] = None
    axis_name: Any = MODEL_AXIS
    wire_dtype: str = "fp32"
    hier_axes: Any = None  # (H, D): two-stage transpose over (host, device)
    inter_wire_dtype: str = "fp32"  # DCN-hop payload of the two-stage path
    prox: Any = None  # the prior (repro.ops.prox.Prox); None = l1 threshold

    def validate(self, distributed: bool) -> "PlanConfig":
        """THE validation site for plan knobs (every entry point funnels
        here via :func:`resolve_plan_config`); returns self for chaining."""
        if self.tail not in ("jnp", "pallas"):
            raise ValueError(f"tail must be 'jnp' or 'pallas', got {self.tail!r}")
        if self.prox is not None and not (
            hasattr(self.prox, "apply") and hasattr(self.prox, "tag")
        ):
            raise ValueError(
                f"prox must be None (the l1 soft threshold) or a "
                f"repro.ops.prox.Prox (apply(x, gamma) + tag); got "
                f"{self.prox!r}"
            )
        prox_mod.check_tail(self.tail, self.prox)
        if not isinstance(self.overlap, int) or self.overlap < 1:
            raise ValueError(f"overlap must be a positive int, got {self.overlap!r}")
        if self.wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"wire_dtype must be one of {sorted(WIRE_DTYPES)}, got "
                f"{self.wire_dtype!r}"
            )
        if self.inter_wire_dtype not in WIRE_DTYPES:
            raise ValueError(
                f"inter_wire_dtype must be one of {sorted(WIRE_DTYPES)}, got "
                f"{self.inter_wire_dtype!r}"
            )
        if not (isinstance(self.axis_name, str) or (
            isinstance(self.axis_name, tuple) and len(self.axis_name) == 2
            and all(isinstance(a, str) for a in self.axis_name)
        )):
            raise ValueError(
                f"axis_name must be one mesh-axis name or a (host, device) "
                f"pair of names, got {self.axis_name!r}"
            )
        if self.hier_axes is not None:
            ok = (
                isinstance(self.hier_axes, tuple) and len(self.hier_axes) == 2
                and all(isinstance(x, int) and x >= 1 for x in self.hier_axes)
            )
            if not ok:
                raise ValueError(
                    f"hier_axes must be a (H, D) tuple of positive ints — "
                    f"the (host, device) factorization of the transform "
                    f"axis — or None for the flat exchange; got "
                    f"{self.hier_axes!r}"
                )
        if not distributed and self.wire_dtype != "fp32":
            raise ValueError(
                f"wire_dtype={self.wire_dtype!r} compresses the transpose "
                f"all-to-all payload of the *distributed* four-step "
                f"transforms — a local (mesh=None) plan has no wire to "
                f"compress and would silently ignore it; pass a mesh or "
                f"leave wire_dtype='fp32' (valid values: "
                f"{sorted(WIRE_DTYPES)})"
            )
        if not distributed and self.hier_axes is not None:
            raise ValueError(
                f"hier_axes={self.hier_axes!r} factors the transform axis "
                f"of a *distributed* (host, device) mesh for the two-stage "
                f"hierarchical transpose — a local (mesh=None) plan has no "
                f"mesh axes to factor; pass a hierarchical mesh "
                f"(repro.dist.compat.make_hier_mesh) or leave "
                f"hier_axes=None (valid values: None or a (H, D) tuple)"
            )
        if self.hier_axes is None and self.inter_wire_dtype != "fp32":
            raise ValueError(
                f"inter_wire_dtype={self.inter_wire_dtype!r} compresses the "
                f"inter-host hops of the *hierarchical* two-stage transpose "
                f"— without hier_axes there is no inter-host tier and it "
                f"would be silently ignored; set hier_axes=(H, D) or leave "
                f"inter_wire_dtype='fp32' (valid values: "
                f"{sorted(WIRE_DTYPES)})"
            )
        if not distributed and (
            self.rfft or self.overlap != 1 or self.batch_axis is not None
        ):
            raise ValueError(
                "rfft/overlap are distributed-backend knobs (the sharded "
                "four-step transforms), and batch_axis names a mesh axis; "
                "pass a mesh to use them — a local plan would silently "
                "ignore them"
            )
        if (self.n1 is not None and self.n1 < 1) or (
            self.n2 is not None and self.n2 < 1
        ):
            raise ValueError(f"n1/n2 must be positive, got {self.n1}/{self.n2}")
        return self

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        for key in ("batch_axis", "axis_name", "hier_axes"):
            if isinstance(d[key], tuple):
                d[key] = list(d[key])
        d["prox"] = prox_mod.prox_to_dict(self.prox)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "PlanConfig":
        d = dict(d)
        for key in ("batch_axis", "axis_name", "hier_axes"):
            if isinstance(d.get(key), list):
                d[key] = tuple(d[key])
        if d.get("prox") is not None:
            d["prox"] = prox_mod.prox_from_dict(d["prox"])
        return cls(**d)

    def describe(self) -> str:
        """Compact human-readable tag (bench rows, tuner logs, serve bucket
        keys — every knob that changes the compiled program must show)."""
        parts = [
            f"n1xn2={self.n1}x{self.n2}" if self.n1 else "n1xn2=auto",
            f"rfft={'on' if self.rfft else 'off'}",
            f"overlap={self.overlap}",
            f"tail={self.tail}",
        ]
        if not self.fused:
            parts.append("unfused")
        if self.batch_axis is not None:
            parts.append(f"batch_axis={self.batch_axis}")
        if self.wire_dtype != "fp32":
            parts.append(f"wire={self.wire_dtype}")
        if self.hier_axes is not None:
            parts.append(f"hier={self.hier_axes[0]}x{self.hier_axes[1]}")
        elif isinstance(self.axis_name, tuple):
            parts.append("hier=flat")  # factored axis, flat exchange
        if self.inter_wire_dtype != "fp32":
            parts.append(f"inter_wire={self.inter_wire_dtype}")
        if self.prox is not None:
            # the prior changes the compiled z-update (and serve engines must
            # never share across priors) — every non-default prox shows
            parts.append(f"prox={self.prox.tag}")
        return " ".join(parts)


def resolve_plan_config(config: Optional[PlanConfig], *, distributed: bool,
                        **knobs) -> PlanConfig:
    """``config=`` / legacy-kwargs reconciliation + the single validation.

    ``knobs`` are the legacy keyword arguments with ``None`` meaning "not
    given": either a full ``config`` is passed (and every legacy knob must
    stay unset — mixing the two would silently shadow fields), or a
    ``PlanConfig`` is constructed from whichever knobs were given, defaults
    filling the rest.
    """
    set_knobs = {k: v for k, v in knobs.items() if v is not None}
    if config is not None:
        if set_knobs:
            raise ValueError(
                f"pass config=PlanConfig(...) or individual plan knobs, not "
                f"both (got config= plus {sorted(set_knobs)})"
            )
        cfg = config
    else:
        cfg = PlanConfig(**set_knobs)
    return cfg.validate(distributed)


def _resolve_axes(cfg: PlanConfig, mesh):
    """Mesh-dependent half of the hier validation (the shape-only half lives
    in :meth:`PlanConfig.validate`): resolve the transform axis — one mesh
    axis name, or the (host, device) pair when the plan is hierarchical or
    the config names a factored axis — and check ``hier_axes`` against the
    mesh's actual extents.  Returns ``(axis_name, hier_axes)``.
    """
    if cfg.hier_axes is None and not isinstance(cfg.axis_name, tuple):
        return cfg.axis_name, None
    axes = (
        cfg.axis_name if isinstance(cfg.axis_name, tuple)
        else (HOST_AXIS, DEVICE_AXIS)
    )
    missing = [a for a in axes if a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"hierarchical plans shard the transform over the mesh-axis "
            f"pair {axes}, but this mesh has axes "
            f"{tuple(mesh.axis_names)} (missing {missing}); build the mesh "
            f"with repro.dist.compat.make_hier_mesh(data, host, device) or "
            f"pass axis_name=(host_axis, device_axis) naming existing axes"
        )
    extents = (mesh.shape[axes[0]], mesh.shape[axes[1]])
    if cfg.hier_axes is not None and tuple(cfg.hier_axes) != extents:
        raise ValueError(
            f"hier_axes={cfg.hier_axes} does not factor this mesh's "
            f"transform extent: axes {axes} have extents {extents} "
            f"(H x D = {extents[0] * extents[1]}); valid value: "
            f"hier_axes={extents}"
        )
    return axes, cfg.hier_axes


def _transform_extent(mesh, axis_name) -> int:
    """Total shard count p of the (possibly factored) transform axis."""
    if isinstance(axis_name, str):
        return mesh.shape[axis_name]
    return mesh.shape[axis_name[0]] * mesh.shape[axis_name[1]]


class PlannedOperator:
    """Mask-form ``diag(mask) C`` on the plan's mesh, acting on flat arrays.

    This is the distributed RecoveryOperator view: ``matvec``/``rmatvec``
    take flat (..., n) signals, run the sharded four-step transforms, and
    return flat results — so the core drivers' metric/objective code and
    ``RecoveryProblem`` construction work unchanged.  Measurements are in
    the scattered full-length convention (``project_back`` is the identity).
    """

    def __init__(self, plan: "ExecutionPlan"):
        self._plan = plan

    @property
    def n(self) -> int:
        return self._plan.n1 * self._plan.n2

    @property
    def m(self) -> int:
        return self.n  # mask form: measurements live scattered, length n

    def matvec(self, x: Array) -> Array:
        pl = self._plan
        x2d = layout_2d(x, pl.n1, pl.n2)
        return unlayout_2d(pl.mask2d * pl._apply(x2d, transpose=False))

    def rmatvec(self, r: Array) -> Array:
        # true adjoint of diag(mask) C: C^T diag(mask).  Solver residuals are
        # already masked (mask * r == r), but the protocol promises A^T r for
        # arbitrary full-length r.
        pl = self._plan
        r2d = pl.mask2d * layout_2d(r, pl.n1, pl.n2)
        return unlayout_2d(pl._apply(r2d, transpose=True))

    def operator_norm_bound(self) -> Array:
        if self._plan.norm_bound is None:
            raise ValueError("this plan carries no spectrum norm bound")
        return self._plan.norm_bound

    def project_back(self, y: Array) -> Array:
        return y  # already scattered full-length


@dataclasses.dataclass(frozen=True, eq=False)
class ExecutionPlan:
    """An operator lowered to an execution backend (see module docstring).

    Local plans (``mesh is None``) carry only the operator and knobs;
    distributed plans additionally hold the column-sharded spectrum block
    ``spec2d``, the row-sharded measurement mask ``mask2d``, and the
    four-step factorization ``n1 x n2``.
    """

    op: Any = None
    mesh: Any = None
    n1: Optional[int] = None
    n2: Optional[int] = None
    rfft: bool = False
    overlap: int = 1
    tail: str = "jnp"
    fused: bool = True
    batch_axis: Any = None
    axis_name: Any = MODEL_AXIS
    wire_dtype: str = "fp32"
    hier_axes: Any = None
    inter_wire_dtype: str = "fp32"
    prox: Any = None
    spec2d: Any = None
    mask2d: Any = None
    norm_bound: Any = None

    # -- basic facts -------------------------------------------------------
    @property
    def is_distributed(self) -> bool:
        return self.mesh is not None

    @property
    def hier(self) -> bool:
        """Whether transposes run as the two-stage hierarchical exchange."""
        return self.hier_axes is not None

    @property
    def config(self) -> PlanConfig:
        """The knobs of this plan as one :class:`PlanConfig` — the value the
        tuner caches and the parity tests compare across entry points."""
        return PlanConfig(
            rfft=self.rfft,
            overlap=self.overlap,
            tail=self.tail,
            fused=self.fused,
            batch_axis=self.batch_axis,
            n1=self.n1,
            n2=self.n2,
            axis_name=self.axis_name,
            wire_dtype=self.wire_dtype,
            hier_axes=self.hier_axes,
            inter_wire_dtype=self.inter_wire_dtype,
            prox=self.prox,
        )

    @property
    def operator(self):
        """The RecoveryOperator view of this plan: the original operator on
        one device, or the mask-form planned operator on the mesh."""
        if not self.is_distributed:
            return self.op
        return PlannedOperator(self)

    def matvec(self, x: Array) -> Array:
        return self.operator.matvec(x)

    def rmatvec(self, y: Array) -> Array:
        return self.operator.rmatvec(y)

    # -- sharding specs ----------------------------------------------------
    # delegated to repro.dist.fft's spec builders, which own the device-major
    # sharding convention for factored (host, device) transform axes
    # (batched arrays keep their leading batch entry even when batch_axis is
    # None — "batched but replicated" must not collapse to the 2-dim spec)
    def _row(self, batched: bool) -> P:
        if batched:
            return P(self.batch_axis, *row_spec(self.axis_name))
        return row_spec(self.axis_name)

    def _col(self, batched: bool) -> P:
        if batched:
            return P(self.batch_axis, *col_spec(self.axis_name))
        return col_spec(self.axis_name)

    # -- planned applications ---------------------------------------------
    def _apply(self, x2d: Array, transpose: bool) -> Array:
        """One sharded circulant application on layout-2d arrays (two
        transpose-collectives; half-spectrum when ``rfft``)."""
        local = rmatvec_local if self.rfft else matvec_local
        batched = x2d.ndim > 2
        fn = shard_map(
            functools.partial(
                local,
                axis_name=self.axis_name,
                transpose=transpose,
                overlap=self.overlap,
                wire_dtype=self.wire_dtype,
                hier=self.hier,
                inter_wire_dtype=self.inter_wire_dtype,
            ),
            mesh=self.mesh,
            in_specs=(self._col(False), self._row(batched)),
            out_specs=self._row(batched),
            check_vma=False,
        )
        return fn(self.spec2d, x2d)

    def _scattered_measurements(self, problem) -> Array:
        """problem.y -> the full-length scattered P^T y the mesh works in."""
        y = problem.y
        n = self.n1 * self.n2
        if y.shape[-1] == n:
            return y
        if hasattr(problem.op, "project_back"):
            return problem.op.project_back(y)
        raise ValueError(
            f"distributed plans need measurements of length n={n} (scattered "
            f"P^T y) or an operator with project_back; got length {y.shape[-1]}"
        )

    # -- steppers (consumed by repro.core.solvers drivers) -----------------
    def build_stepper(self, problem, method: str, alpha=1e-4, rho=0.1,
                      sigma=0.1, tau=None, prox=None):
        """Lower (problem, method) to a core ``Stepper`` on this backend.

        ``prox=None`` defaults to the plan's own ``prox`` knob."""
        prox = prox if prox is not None else self.prox
        if not self.is_distributed:
            from repro.core.solvers import make_stepper

            return make_stepper(
                problem, method, alpha=alpha, rho=rho, sigma=sigma, tau=tau,
                plan=self, prox=prox,
            )
        if method in _ISTA_METHODS:
            return self._ista_stepper(problem, method, alpha, tau, prox)
        if method == "cpadmm":
            return self._cpadmm_stepper(problem, alpha, rho, sigma, tau, prox)
        raise ValueError(
            f"method {method!r} has no distributed lowering; valid "
            f"distributed methods: ista, fista, cpista, cpadmm"
        )

    def _ista_stepper(self, problem, method: str, alpha, tau, prox=None):
        """Distributed CPISTA/FISTA: the core step math verbatim, with the
        matvecs lowered to planned four-step transforms.  State lives in
        the sharded (n1, n2) layout; ``extract`` flattens locally."""
        from repro.core import ista as ista_mod
        from repro.core.solvers import Stepper

        y_full = self._scattered_measurements(problem)
        if y_full.ndim > 2:
            raise ValueError("distributed plans support one leading batch axis")
        y2d = layout_2d(y_full, self.n1, self.n2)
        dt = y_full.dtype
        op2d = _Layout2DOperator(self)
        tau_v = (
            jnp.asarray(tau, dt) if tau is not None else ista_mod.default_tau(op2d)
        )
        p = ista_mod.IstaParams(alpha=jnp.asarray(alpha, dt), tau=tau_v)
        step_fn = ista_mod.fista_step if method == "fista" else ista_mod.ista_step
        # the dist ISTA step applies its prox at the global jit level (only
        # the matvecs are shard_mapped), so any prior threads straight in —
        # non-elementwise priors just need the flat-signal view of the
        # (n1, n2)-layout iterate (NOT a plain reshape: the four-step layout
        # is strided, see dist.fft.layout_2d)
        step_prox = prox if prox_mod.is_elementwise(prox) else _LayoutProx(
            prox, self.n1, self.n2
        )
        zeros = jnp.zeros_like(y2d)
        # per-signal momentum (batch-shaped) — matches ista_init, so frozen /
        # recycled slots keep a solo run's schedule (core.solvers.rearm_slots)
        return Stepper(
            init=lambda: ista_mod.IstaState(
                x=zeros, x_prev=zeros, t_mom=jnp.ones(y_full.shape[:-1], dt)
            ),
            step=lambda s: step_fn(op2d, y2d, s, p, prox=step_prox),
            extract=lambda s: unlayout_2d(s.x),
        )

    def _cpadmm_stepper(self, problem, alpha, rho, sigma, tau, prox=None):
        """Distributed CPADMM: the planned step functions of
        :mod:`repro.dist.recovery` under a per-iteration shard_map.

        Elementwise priors (l1, nonneg-l1) run inside the shard_map step —
        the tail stays local to each shard, and the fused Pallas tail stays
        eligible for l1.  Non-elementwise priors (TV, wavelet) need the whole
        signal: the step splits into the shard_mapped transform core
        (:func:`repro.dist.recovery.dist_cpadmm_core`) plus a global-level
        tail where GSPMD partitions the prox's rolls/reshapes."""
        from repro.core.solvers import Stepper

        y_full = self._scattered_measurements(problem)
        if y_full.ndim > 2:
            raise ValueError("distributed plans support one leading batch axis")
        batched = y_full.ndim > 1
        pty2d = layout_2d(y_full, self.n1, self.n2)
        dt = y_full.dtype
        t = 1.0 if tau is None else tau
        p = DistCpadmmParams(
            alpha=jnp.asarray(alpha, dt),
            rho=jnp.asarray(rho, dt),
            sigma=jnp.asarray(sigma, dt),
            tau1=jnp.asarray(t, dt),
            tau2=jnp.asarray(t, dt),
        )
        # Alg. 3 line 2, sharded: both inner inverses are local pointwise ops
        b_spec = spectral.gram_inverse_spectrum(self.spec2d, p.rho, p.sigma)
        d_diag = jnp.where(
            self.mask2d > 0, 1.0 / (1.0 + p.rho), 1.0 / p.rho
        ).astype(dt)
        rowS, rowB = self._row(False), self._row(batched)
        state_spec = DistCpadmmState(*(rowB,) * 5)
        zeros = jnp.zeros_like(pty2d)
        init = lambda: DistCpadmmState(zeros, zeros, zeros, zeros, zeros)

        if prox_mod.is_elementwise(prox):
            step_fn = dist_cpadmm_step_fused if self.fused else dist_cpadmm_step

            def local_step(spec, bs, dd, pty, state, pp):
                return step_fn(
                    spec, bs, dd, pty, state, pp,
                    self.axis_name, self.rfft, self.overlap, self.tail,
                    self.wire_dtype, self.hier, self.inter_wire_dtype,
                    prox=prox,
                )

            step_sm = shard_map(
                local_step,
                mesh=self.mesh,
                in_specs=(
                    self._col(False), self._col(False), rowS, rowB, state_spec,
                    DistCpadmmParams(*(P(),) * 5),
                ),
                out_specs=state_spec,
                check_vma=False,
            )
            return Stepper(
                init=init,
                step=lambda s: step_sm(self.spec2d, b_spec, d_diag, pty2d, s, p),
                extract=lambda s: unlayout_2d(s.z),
            )

        core_sm = self._cpadmm_core_sm(rowB)
        lprox = _LayoutProx(prox, self.n1, self.n2)

        def hybrid_step(s):
            x, cx = core_sm(self.spec2d, b_spec, s.v + s.mu, s.z - s.nu, p)
            v = d_diag * (pty2d + p.rho * (cx - s.mu))
            z = lprox.apply(x + s.nu, p.alpha / p.sigma)
            mu = s.mu + p.tau1 * (v - cx)
            nu = s.nu + p.tau2 * (x - z)
            return DistCpadmmState(x=x, v=v, z=z, mu=mu, nu=nu)

        return Stepper(
            init=init,
            step=hybrid_step,
            extract=lambda s: unlayout_2d(s.z),
        )

    def _cpadmm_core_sm(self, rowB: P):
        """shard_map of the CPADMM transform core (x-update + C x) — the
        non-elementwise-prior step runs this inside an otherwise global-level
        iteration so the prior sees whole signals."""
        col = self._col(False)

        def local_core(spec, bs, vmu, znu, pp):
            return dist_cpadmm_core(
                spec, bs, vmu, znu, pp,
                self.axis_name, self.rfft, self.overlap,
                self.wire_dtype, self.hier, self.inter_wire_dtype,
            )

        return shard_map(
            local_core,
            mesh=self.mesh,
            in_specs=(col, col, rowB, rowB, DistCpadmmParams(*(P(),) * 5)),
            out_specs=(rowB, rowB),
            check_vma=False,
        )

    # -- abstract iteration block (dry-run / HLO-analysis entry point) -----
    def cpadmm_block(self, iters: int, alpha=1e-4, rho=0.01, sigma=0.01,
                     tau=1.0):
        """Jitted ``block(spec, b_spec, d_diag, pty, state) -> state`` running
        ``iters`` scanned iterations inside one shard_map — a pure function
        of its operands, so ``.lower()`` with ShapeDtypeStructs exposes the
        compiled HLO (launch/cs_dryrun.py's roofline walks it).  The state
        (and pty) carry a leading batch dim sharded over ``batch_axis``.

        With a non-elementwise plan ``prox`` (TV/wavelet) the block is the
        hybrid split instead — shard_mapped transform core, global prox tail
        — jitted with explicit in_shardings so ``.lower()`` still exposes the
        partitioned HLO the tuner's cost model walks."""
        p = DistCpadmmParams(
            *(jnp.float32(v) for v in (alpha, rho, sigma, tau, tau))
        )
        rowS, rowB, col = self._row(False), self._row(True), self._col(False)
        state_spec = DistCpadmmState(*(rowB,) * 5)

        if prox_mod.is_elementwise(self.prox):
            prox = self.prox
            step_fn = dist_cpadmm_step_fused if self.fused else dist_cpadmm_step

            def block(spec, b_spec, d_diag, pty, state):
                def body(s, _):
                    return step_fn(
                        spec, b_spec, d_diag, pty, s, p,
                        self.axis_name, self.rfft, self.overlap, self.tail,
                        self.wire_dtype, self.hier, self.inter_wire_dtype,
                        prox=prox,
                    ), None

                state, _ = lax.scan(body, state, None, length=iters)
                return state

            return jax.jit(
                shard_map(
                    block,
                    mesh=self.mesh,
                    in_specs=(col, col, rowS, rowB, state_spec),
                    out_specs=state_spec,
                    check_vma=False,
                )
            )

        core_sm = self._cpadmm_core_sm(rowB)
        lprox = _LayoutProx(self.prox, self.n1, self.n2)

        def hybrid_block(spec, b_spec, d_diag, pty, state):
            def body(s, _):
                x, cx = core_sm(spec, b_spec, s.v + s.mu, s.z - s.nu, p)
                v = d_diag * (pty + p.rho * (cx - s.mu))
                z = lprox.apply(x + s.nu, p.alpha / p.sigma)
                mu = s.mu + p.tau1 * (v - cx)
                nu = s.nu + p.tau2 * (x - z)
                return DistCpadmmState(x=x, v=v, z=z, mu=mu, nu=nu), None

            state, _ = lax.scan(body, state, None, length=iters)
            return state

        sh = lambda spec: jax.sharding.NamedSharding(self.mesh, spec)
        return jax.jit(
            hybrid_block,
            in_shardings=(
                sh(col), sh(col), sh(rowS), sh(rowB),
                DistCpadmmState(*(sh(rowB),) * 5),
            ),
        )


class _LayoutProx:
    """A Prox adapted to the four-step (n1, n2) iterate layout.

    ``layout_2d`` is *strided* (``A[j1, j2] = x[j1 + n1*j2]``), not a
    row-major reshape, so a flat-signal prox applied to a distributed
    iterate must round-trip through ``unlayout_2d``/``layout_2d`` — a plain
    reshape would scramble the signal and be silently wrong.  Under the
    global jit both are data movements GSPMD partitions."""

    def __init__(self, prox, n1: int, n2: int):
        self._prox = prox
        self._n1 = n1
        self._n2 = n2

    def apply(self, a2d: Array, gamma) -> Array:
        flat = self._prox.apply(unlayout_2d(a2d), gamma)
        return layout_2d(flat, self._n1, self._n2)


class _Layout2DOperator:
    """The plan's operator view in the native (n1, n2) sharded layout —
    what the ISTA/FISTA step math consumes so iterates never leave the
    sharded layout between iterations."""

    def __init__(self, plan: ExecutionPlan):
        self._plan = plan

    def matvec(self, x2d: Array) -> Array:
        pl = self._plan
        return pl.mask2d * pl._apply(x2d, transpose=False)

    def rmatvec(self, r2d: Array) -> Array:
        # adjoint of diag(mask) C (the mask multiply is a bitwise no-op on
        # the already-masked residuals the ISTA step feeds in)
        pl = self._plan
        return pl._apply(pl.mask2d * r2d, transpose=True)

    def operator_norm_bound(self) -> Array:
        if self._plan.norm_bound is None:
            raise ValueError(
                "plan has no operator norm bound; pass tau explicitly"
            )
        return self._plan.norm_bound


def _wire_guard(wire_plan: ExecutionPlan) -> ExecutionPlan:
    """Error-controlled wire precision: probe one matvec of the demoted-wire
    plan against the fp32-wire twin and fall back when the relative error
    exceeds :data:`WIRE_ERROR_BOUND` (``REPRO_WIRE_ERROR_BOUND`` env).

    The probe is cheap (one planned matvec each way on a unit-norm random
    signal) and catches both gradual quantization loss and hard fp16
    overflow (payload magnitudes past float16's 65504 max turn the probe
    error non-finite, which fails the ``err <= bound`` check).  Both tiers
    are guarded at once: a demoted ``inter_wire_dtype`` (hierarchical DCN
    hops) trips the probe exactly like a demoted ``wire_dtype``, and the
    fallback restores fp32 on both.
    """
    if wire_plan.wire_dtype == "fp32" and wire_plan.inter_wire_dtype == "fp32":
        return wire_plan
    ref_plan = dataclasses.replace(
        wire_plan, wire_dtype="fp32", inter_wire_dtype="fp32"
    )
    n = wire_plan.n1 * wire_plan.n2
    x = jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32)
    x = x / jnp.linalg.norm(x)
    got = wire_plan.matvec(x)
    ref = ref_plan.matvec(x)
    denom = jnp.linalg.norm(ref)
    err = float(jnp.linalg.norm(got - ref) / jnp.where(denom > 0, denom, 1.0))
    bound = WIRE_ERROR_BOUND
    if not err <= bound:  # noqa: SIM300  (NaN/inf must fail the guard too)
        warnings.warn(
            f"wire_dtype={wire_plan.wire_dtype!r} / inter_wire_dtype="
            f"{wire_plan.inter_wire_dtype!r} failed the precision "
            f"guard: relative matvec error {err:.3e} exceeds the bound "
            f"{bound:.1e} (REPRO_WIRE_ERROR_BOUND) — falling back to "
            f"fp32 wires on both tiers",
            RuntimeWarning,
            stacklevel=3,
        )
        return ref_plan
    return wire_plan


def _plan_with_config(op, mesh, cfg: PlanConfig) -> ExecutionPlan:
    """Lower ``op`` under an already-validated ``PlanConfig``."""
    if mesh is None:
        return ExecutionPlan(op=op, tail=cfg.tail, fused=cfg.fused, prox=cfg.prox)
    if hasattr(op, "circ"):  # PartialCirculant: mask = indicator of omega
        circ, omega = op.circ, op.omega
    elif hasattr(op, "spec") and hasattr(op, "col"):  # full Circulant
        circ, omega = op, None
    else:
        raise TypeError(
            f"distributed plans need a (partial) circulant operator, got "
            f"{type(op).__name__}"
        )
    n = circ.n
    axes, hier_axes = _resolve_axes(cfg, mesh)
    p = _transform_extent(mesh, axes)
    n1, n2 = _factorize(n, cfg.n1, cfg.n2, p, cfg.rfft)
    if omega is None:
        mask = jnp.ones((n,), circ.col.dtype)
    else:
        mask = jnp.zeros((n,), circ.col.dtype).at[omega].set(1.0)
    # the spectrum is already stored on the operator (half layout): re-lay it
    # out for the four-step transforms and shard the columns — no transform
    # runs here, so composed spectra (deblur's spec(C)·spec(B)) never round-
    # trip through the time domain
    spec2d = jax.device_put(
        spectral.spectrum_layout_2d(circ.spec, n1, n2, rfft=cfg.rfft, p=p),
        jax.sharding.NamedSharding(mesh, col_spec(axes)),
    )
    built = ExecutionPlan(
        op=op,
        mesh=mesh,
        n1=n1,
        n2=n2,
        rfft=cfg.rfft,
        overlap=cfg.overlap,
        tail=cfg.tail,
        fused=cfg.fused,
        batch_axis=cfg.batch_axis,
        axis_name=axes,
        wire_dtype=cfg.wire_dtype,
        hier_axes=hier_axes,
        inter_wire_dtype=cfg.inter_wire_dtype,
        prox=cfg.prox,
        spec2d=spec2d,
        mask2d=layout_2d(mask, n1, n2),
        norm_bound=op.operator_norm_bound(),
    )
    return _wire_guard(built)


def plan(
    op,
    mesh=None,
    *,
    config: Optional[PlanConfig] = None,
    tune=False,
    batch: Optional[int] = None,
    tune_opts: Optional[dict] = None,
    n1: Optional[int] = None,
    n2: Optional[int] = None,
    rfft: Optional[bool] = None,
    overlap: Optional[int] = None,
    tail: Optional[str] = None,
    fused: Optional[bool] = None,
    batch_axis: Any = None,
    axis_name: Any = None,
    wire_dtype: Optional[str] = None,
    hier_axes: Any = None,
    inter_wire_dtype: Optional[str] = None,
    prox: Any = None,
) -> ExecutionPlan:
    """Lower ``op`` to an execution plan (see module docstring).

    With ``mesh=None`` this is the identity lowering: ``plan(op).operator``
    *is* ``op``, so every matvec is bit-exact with the core path.  With a
    mesh, ``op`` must be a (partial) circulant: the plan lays the operator's
    *stored half spectrum* out into the column-sharded four-step layout
    (``spectral.spectrum_layout_2d`` — pure bookkeeping, no irfft back to
    the first column and no distributed FFT of it, so a composed operator
    like the Sec. 7 deblur spectrum ``spec(C)·spec(B)`` is built and sharded
    exactly once) plus the row-sharded measurement mask, and lowers matvecs
    / solver steps to the four-step transforms.

    Knobs come either as ``config=PlanConfig(...)`` or as the individual
    keyword arguments (a thin compat path producing the same config; mixing
    the two is an error).  ``n1``/``n2`` pick the layout factorization
    (auto-chosen near sqrt(n) when omitted).

    ``tune=True`` (cost model) or ``tune="measure"`` (cost model + wall-clock
    of the top candidates) asks :mod:`repro.ops.tune` to pick the config
    instead; any individual knob that *is* passed becomes a pin restricting
    the candidate space (``config=`` cannot be combined with ``tune`` —
    a full config leaves nothing to tune).  ``batch`` sizes the tuning
    workload (leading batch of signals); ``tune_opts`` forwards extras to
    :func:`repro.ops.tune.tuned_config` (e.g. ``cache=``, ``top_k=``).
    """
    if tune:
        if config is not None:
            raise ValueError(
                "tune= and config= are mutually exclusive: a full PlanConfig "
                "leaves nothing to tune (pass individual knobs to pin them)"
            )
        from . import tune as tune_mod

        pins = {
            k: v
            for k, v in dict(
                n1=n1, n2=n2, rfft=rfft, overlap=overlap, tail=tail,
                fused=fused, batch_axis=batch_axis, axis_name=axis_name,
                wire_dtype=wire_dtype, hier_axes=hier_axes,
                inter_wire_dtype=inter_wire_dtype, prox=prox,
            ).items()
            if v is not None
        }
        mode = tune if isinstance(tune, str) else "model"
        cfg = tune_mod.tuned_config(
            op, mesh, mode=mode, batch=batch, pins=pins, **(tune_opts or {})
        )
        cfg = cfg.validate(distributed=mesh is not None)
    else:
        cfg = resolve_plan_config(
            config,
            distributed=mesh is not None,
            n1=n1, n2=n2, rfft=rfft, overlap=overlap, tail=tail,
            fused=fused, batch_axis=batch_axis, axis_name=axis_name,
            wire_dtype=wire_dtype, hier_axes=hier_axes,
            inter_wire_dtype=inter_wire_dtype, prox=prox,
        )
    return _plan_with_config(op, mesh, cfg)


def plan_from_parts(
    mesh,
    spec2d=None,
    mask2d=None,
    *,
    config: Optional[PlanConfig] = None,
    n1: Optional[int] = None,
    n2: Optional[int] = None,
    rfft: Optional[bool] = None,
    overlap: Optional[int] = None,
    tail: Optional[str] = None,
    fused: Optional[bool] = None,
    batch_axis: Any = None,
    axis_name: Any = None,
    wire_dtype: Optional[str] = None,
    hier_axes: Any = None,
    inter_wire_dtype: Optional[str] = None,
    prox: Any = None,
) -> ExecutionPlan:
    """Distributed plan from pre-sharded parts instead of an operator.

    For callers that already live in the sharded representation: the
    deprecation shim ``repro.dist.recovery.make_dist_cpadmm`` (spectrum and
    mask arrive as arrays) and the abstract lowerings in
    ``launch/cs_dryrun.py`` and ``ops/tune.py`` (no concrete arrays at all —
    only :meth:`ExecutionPlan.cpadmm_block` is used).  ``spec2d`` is the
    column-sharded spectrum of C with the matching ``rfft`` layout;
    ``mask2d`` the row-sharded 0/1 measurement indicator.  Accepts
    ``config=PlanConfig(...)`` like :func:`plan`; with no operator to read
    ``n`` from, the factorization ``n1 x n2`` must be concrete either way.
    """
    cfg = resolve_plan_config(
        config,
        distributed=True,
        n1=n1, n2=n2, rfft=rfft, overlap=overlap, tail=tail,
        fused=fused, batch_axis=batch_axis, axis_name=axis_name,
        wire_dtype=wire_dtype, hier_axes=hier_axes,
        inter_wire_dtype=inter_wire_dtype, prox=prox,
    )
    if cfg.n1 is None or cfg.n2 is None:
        raise ValueError(
            "plan_from_parts has no operator to infer n from: the config "
            "must carry a concrete n1 x n2 factorization"
        )
    axes, hier = _resolve_axes(cfg, mesh)
    norm = jnp.max(jnp.abs(spec2d)) if spec2d is not None else None
    # no precision guard here: this entry point also serves the abstract
    # lowerings (no concrete spec2d at all) — plan() is the guarded route
    return ExecutionPlan(
        mesh=mesh,
        n1=cfg.n1,
        n2=cfg.n2,
        rfft=cfg.rfft,
        overlap=cfg.overlap,
        tail=cfg.tail,
        fused=cfg.fused,
        batch_axis=cfg.batch_axis,
        axis_name=axes,
        wire_dtype=cfg.wire_dtype,
        hier_axes=hier,
        inter_wire_dtype=cfg.inter_wire_dtype,
        prox=cfg.prox,
        spec2d=spec2d,
        mask2d=mask2d,
        norm_bound=norm,
    )

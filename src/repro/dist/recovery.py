"""Planned CPADMM step functions (paper Alg. 3) over the sharded four-step FFT.

This module holds the *per-iteration math* of distributed CPADMM and
nothing else: the solver drivers live in ``repro.core.solvers`` and reach
these steps through an execution plan (``repro.ops.plan(op, mesh)``), which
is also how distributed CPISTA/FISTA run — same drivers, planned matvecs.
``make_dist_cpadmm`` remains only as a deprecation shim over that API.

The single-device solver (``repro.core.admm.cpadmm_step``) does per
iteration three circulant applications — C^T, B = (rho C^T C + sigma I)^{-1}
and C — i.e. six length-n transforms, plus elementwise work.  Here the same
iteration runs with every array sharded in the :mod:`repro.dist.fft` layout:

    spectra  (spec of C, spec of B)      column-sharded  P(None, model)
    iterates (x, v, z, mu, nu), d_diag,
    P^T y                                row-sharded     P(model, None)

The Woodbury/spectral inverse B never leaves the frequency domain: its
spectrum is elementwise ``1 / (rho |spec|^2 + sigma)`` computed on the local
column block, so the x-update's "inversion" stays a pointwise multiply per
device — Andrecut-style: the per-device hot path is pointwise spectral ops,
all cross-device traffic is the FFT transpose-collective.

Two step variants:

    dist_cpadmm_step        paper-faithful: 3 separate circulant applies,
                            6 transforms = 6 all-to-alls per iteration.
    dist_cpadmm_step_fused  the x-update is formed directly in the frequency
                            domain (B and C^T fuse into one local spectral
                            multiply — Alg. 3 line 2 never materializes
                            C^T(v+mu) in the time domain) and the remaining
                            transforms are batched: one stacked forward FFT
                            (v+mu, z-nu) and one stacked inverse FFT
                            (x, Cx), so an iteration costs 2 all-to-alls
                            instead of 6.  The soft-threshold and both dual
                            updates collapse into a single elementwise pass.

Both steps take ``rfft=True`` to run on the half-spectrum transforms of
:mod:`repro.dist.fft` (real iterates, Hermitian spectra): half the local FFT
flops and half the all-to-all wire bytes per iteration, same all-to-all
count.  The spectra (``spec``, ``b_spec``) must then be in the half layout
(from ``make_dist_spectrum(..., rfft=True)``).

Batching over the data axis: every step broadcasts over leading batch axes,
and ``make_dist_cpadmm(..., batch_axis='data')`` shards a leading batch of B
signals over the mesh's data axis while the model axis keeps the within-
signal FFT sharding — all B signals share each transform's single
all-to-all, which is the Andrecut-style many-signals-at-once form of the
paper's workload.

Two iteration-critical-path knobs ride every step:

    overlap=K   each transform's transpose-collective is split into K
                chunked all-to-alls overlapped with the first local FFT
                stage (repro.dist.fft docstring) — same payload (pad bytes
                only when K does not divide the chunk axis), same result,
                up to (K-1)/K of the wire hidden behind compute.
    tail        'jnp' (default) keeps the elementwise tail as XLA-fused
                jnp ops; 'pallas' routes it through the fused
                kernels/cpadmm_tail VMEM-resident kernel (one pass for the
                v-update, soft-threshold, and both dual updates).

Both agree with the single-device solver to float32 roundoff on the same
problem (tests/test_dist_equiv.py, tests/dist_progs/recovery_prog.py,
tests/dist_progs/batched_recovery_prog.py).
"""

from __future__ import annotations

import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.admm import cpadmm_tail

from .compat import shard_map
from .fft import (
    MODEL_AXIS,
    col_spec,
    fft2_local,
    ifft2_local,
    irfft2_local,
    layout_2d,
    rfft2_local,
    row_spec,
    unlayout_2d,
)

Array = jax.Array


def _transforms(
    rfft: bool, n2: int, cdtype, axis_name: str, overlap: int = 1,
    wire_dtype: str = "fp32", hier: bool = False,
    inter_wire_dtype: str = "fp32",
):
    """(forward, inverse) local transform pair: real block <-> spectrum block.

    The full-complex pair casts to the spectrum dtype and takes the real
    part on the way back; the rfft pair stays real-in/real-out in the half
    layout (``n2`` is the full column count the half spectrum unfolds to).
    ``overlap`` selects the chunked overlapped transpose in both directions;
    ``wire_dtype`` demotes each transpose's all-to-all payload on the wire
    (twiddles and accumulation stay fp32 locally — repro.dist.fft).
    ``hier`` (with a (host, device) ``axis_name``) runs each transpose as
    the two-stage hierarchical exchange, ``inter_wire_dtype`` demoting only
    its inter-host hops.
    """
    if rfft:
        fwd = lambda r: rfft2_local(
            r, axis_name, overlap, wire_dtype, hier, inter_wire_dtype
        )
        inv = lambda F: irfft2_local(
            F, n2, axis_name, overlap, wire_dtype, hier, inter_wire_dtype
        )
    else:
        fwd = lambda r: fft2_local(
            r.astype(cdtype), axis_name, overlap, wire_dtype, hier,
            inter_wire_dtype,
        )
        inv = lambda F: jnp.real(ifft2_local(
            F, axis_name, overlap, wire_dtype, hier, inter_wire_dtype
        ))
    return fwd, inv


def _tail(tail: str, prox=None):
    """Elementwise-tail dispatch: pure-jnp math or the fused Pallas kernel.

    The Pallas path compiles for real on TPU and runs in interpret mode
    elsewhere (CPU tests), mirroring the repo-wide kernel convention.  The
    fused kernel bakes in the l1 soft threshold, so ``tail='pallas'`` with
    any other prior is refused (``repro.ops.prox.check_tail``); ``'jnp'``
    composes every elementwise prior through the shared jnp tail
    (``core.admm.cpadmm_tail``).  (Non-elementwise priors never reach here
    — the plan layer runs them at the global level via
    :func:`dist_cpadmm_core`.)
    """
    from repro.ops.prox import check_tail

    check_tail(tail, prox)
    if tail == "pallas":
        from repro.kernels.cpadmm_tail.ops import fused_cpadmm_tail, interpret_default

        interpret = interpret_default()

        def run(x, cx, d_diag, pty, mu, nu, p):
            return fused_cpadmm_tail(
                x, cx, d_diag, pty, mu, nu,
                p.rho, p.alpha / p.sigma, p.tau1, p.tau2,
                interpret=interpret,
            )

        return run
    if tail not in ("jnp", "pallas"):
        raise ValueError(f"tail must be 'jnp' or 'pallas', got {tail!r}")
    if prox is None:
        return cpadmm_tail

    def run(x, cx, d_diag, pty, mu, nu, p):
        return cpadmm_tail(x, cx, d_diag, pty, mu, nu, p, prox=prox)

    return run


class DistCpadmmParams(NamedTuple):
    """Alg. 3 hyperparameters (same meaning as core.admm.CpadmmParams)."""

    alpha: Array  # l1 weight
    rho: Array  # splitting weight for v = C x
    sigma: Array  # splitting weight for z = x
    tau1: Array  # dual step for mu
    tau2: Array  # dual step for nu


class DistCpadmmState(NamedTuple):
    """Row-sharded iterates, all in the (..., n1, n2) signal layout."""

    x: Array  # primal estimate
    v: Array  # splitting variable, v ~= C x
    z: Array  # l1 auxiliary (the recovered signal)
    mu: Array  # scaled dual for v = C x
    nu: Array  # scaled dual for z = x


def dist_cpadmm_step(
    spec: Array,
    b_spec: Array,
    d_diag: Array,
    pty: Array,
    state: DistCpadmmState,
    p: DistCpadmmParams,
    axis_name: str = MODEL_AXIS,
    rfft: bool = False,
    overlap: int = 1,
    tail: str = "jnp",
    wire_dtype: str = "fp32",
    hier: bool = False,
    inter_wire_dtype: str = "fp32",
    prox=None,
) -> DistCpadmmState:
    """One paper-faithful Alg. 3 iteration on local shard blocks.

    spec / b_spec: column-sharded spectra of C and B (half layout when
    ``rfft``).  d_diag: row-sharded diagonal of (P^T P + rho I)^{-1}.
    pty: row-sharded P^T y.  Mirrors ``core.admm.cpadmm_step`` line for
    line; broadcasts over leading batch axes.  ``prox`` must be elementwise
    (this step runs whole inside a shard_map — see :func:`_tail`).
    """
    fwd, inv = _transforms(
        rfft, state.x.shape[-1], spec.dtype, axis_name, overlap, wire_dtype,
        hier, inter_wire_dtype,
    )
    tail_fn = _tail(tail, prox)

    def apply(s: Array, r: Array) -> Array:
        return inv(s * fwd(r))

    # x-update: B (rho C^T (v + mu) + sigma (z - nu))
    rhs = p.rho * apply(jnp.conj(spec), state.v + state.mu) + p.sigma * (
        state.z - state.nu
    )
    x = apply(b_spec, rhs)
    cx = apply(spec, x)
    # elementwise tail: v-update, threshold, both dual updates
    v, z, mu, nu = tail_fn(x, cx, d_diag, pty, state.mu, state.nu, p)
    return DistCpadmmState(x=x, v=v, z=z, mu=mu, nu=nu)


def dist_cpadmm_step_fused(
    spec: Array,
    b_spec: Array,
    d_diag: Array,
    pty: Array,
    state: DistCpadmmState,
    p: DistCpadmmParams,
    axis_name: str = MODEL_AXIS,
    rfft: bool = False,
    overlap: int = 1,
    tail: str = "jnp",
    wire_dtype: str = "fp32",
    hier: bool = False,
    inter_wire_dtype: str = "fp32",
    prox=None,
) -> DistCpadmmState:
    """Fused Alg. 3 iteration: 2 all-to-alls, one elementwise tail.

    The two forward transforms (of v+mu and z-nu) ride one stacked FFT; the
    x-update happens entirely in the frequency domain (B and C^T fuse to one
    local multiply); x and Cx come back through one stacked inverse FFT; the
    threshold and both dual updates are a single elementwise pass (the
    fused Pallas kernel when ``tail='pallas'``).  With ``rfft`` the stacked
    transforms run in the half layout — the x-update multiply is closed
    there because every factor is a Hermitian spectrum.  ``overlap=K``
    chunks both stacked transposes.  Broadcasts over leading batch axes
    (the stack axis leads them).  ``prox`` must be elementwise (see
    :func:`_tail`).
    """
    x, cx = dist_cpadmm_core(
        spec, b_spec, state.v + state.mu, state.z - state.nu, p,
        axis_name, rfft, overlap, wire_dtype, hier, inter_wire_dtype,
    )
    tail_fn = _tail(tail, prox)
    # fused elementwise tail: v-update, threshold, both dual updates
    v, z, mu, nu = tail_fn(x, cx, d_diag, pty, state.mu, state.nu, p)
    return DistCpadmmState(x=x, v=v, z=z, mu=mu, nu=nu)


def dist_cpadmm_core(
    spec: Array,
    b_spec: Array,
    vmu: Array,
    znu: Array,
    p: DistCpadmmParams,
    axis_name: str = MODEL_AXIS,
    rfft: bool = False,
    overlap: int = 1,
    wire_dtype: str = "fp32",
    hier: bool = False,
    inter_wire_dtype: str = "fp32",
) -> tuple:
    """The fused step's transform core: ``(v + mu, z - nu) -> (x, C x)``.

    Exactly the frequency-domain x-update of :func:`dist_cpadmm_step_fused`
    (which calls this, so the two can never drift): one stacked forward
    FFT, the fused local B·C^T multiply, one stacked inverse FFT.  Split
    out so the plan layer can shard_map *only* the transforms when the
    prior is non-elementwise (TV/wavelet) — the tail then runs at the
    global jit level where the prox sees whole signals.
    """
    fwd_t, inv_t = _transforms(
        rfft, vmu.shape[-1], spec.dtype, axis_name, overlap, wire_dtype,
        hier, inter_wire_dtype,
    )
    fwd = fwd_t(jnp.stack([vmu, znu]))
    w, zf = fwd[0], fwd[1]
    xf = b_spec * (p.rho * jnp.conj(spec) * w + p.sigma * zf)  # spectrum of x
    inv = inv_t(jnp.stack([xf, spec * xf]))
    return inv[0], inv[1]


# --------------------------------------------------------------------------
# global drivers
# --------------------------------------------------------------------------


def make_dist_spectrum(mesh, axis_name: str = MODEL_AXIS, rfft: bool = False):
    """Jitted: row-sharded layout_2d(first column) -> column-sharded spectrum.

    ``rfft=True`` yields the half-spectrum layout (n1, padded nf columns)
    that the rfft solver path consumes.
    """

    def to_spec(col2d: Array) -> Array:
        if rfft:
            return rfft2_local(col2d, axis_name)
        dt = jnp.complex128 if col2d.dtype == jnp.float64 else jnp.complex64
        return fft2_local(col2d.astype(dt), axis_name)

    return jax.jit(
        shard_map(
            to_spec,
            mesh=mesh,
            in_specs=(row_spec(axis_name),),
            out_specs=col_spec(axis_name),
            check_vma=False,
        )
    )


def make_dist_cpadmm(
    mesh,
    n1: int,
    n2: int,
    iters: int,
    fused: bool = False,
    axis_name: str = MODEL_AXIS,
    rfft: bool = False,
    batch_axis: str | None = None,
    overlap: int = 1,
    tail: str = "jnp",
    wire_dtype: str = "fp32",
):
    """DEPRECATED shim: jitted solver(spec2d, mask2d, y2d, alpha, rho, sigma).

    .. deprecated:: 0.1.0
        Will be **removed in repro 0.2.0**.  Not re-exported from
        ``repro.dist`` — reachable only by this full path until removal.

    The bespoke distributed driver this factory used to build is gone — the
    unified path is::

        pl = repro.ops.plan(op, mesh, rfft=..., overlap=..., tail=...)
        z, trace = repro.core.solvers.solve(problem, 'cpadmm', plan=pl)

    which also unlocks solve_until / solve_checkpointed / metric traces on
    the mesh.  This shim keeps the old call signature working by building a
    plan from the pre-sharded parts and running the same ``solve`` driver;
    output is pinned identical to the plan route (tests/test_plan.py).
    """
    warnings.warn(
        "make_dist_cpadmm is deprecated and will be removed in repro 0.2.0: "
        "build a repro.ops.plan and call repro.core.solvers.solve(..., "
        "method='cpadmm', plan=...) instead",
        DeprecationWarning,
        stacklevel=2,
    )
    if batch_axis is not None and batch_axis not in mesh.axis_names:
        raise ValueError(f"batch_axis {batch_axis!r} not in mesh axes {mesh.axis_names}")

    def run(spec2d, mask2d, y2d, alpha, rho, sigma):
        from repro.core.solvers import RecoveryProblem, solve
        from repro.ops import plan_from_parts

        pl = plan_from_parts(
            mesh, spec2d, mask2d,
            n1=n1, n2=n2, rfft=rfft, overlap=overlap, tail=tail, fused=fused,
            batch_axis=batch_axis, axis_name=axis_name, wire_dtype=wire_dtype,
        )
        prob = RecoveryProblem(op=pl.operator, y=unlayout_2d(y2d))
        z, _ = solve(
            prob, "cpadmm", iters=iters, record_every=iters,
            alpha=alpha, rho=rho, sigma=sigma, plan=pl,
        )
        return layout_2d(z, n1, n2)

    return jax.jit(run)

"""Pallas-kernel-backed solver steps (TPU execution path).

The solvers in ista.py/admm.py are written against pure-jnp circulant ops
(XLA fuses them well, and on CPU interpret-mode Pallas would be pure
overhead).  On TPU the hot loops swap in the kernels from repro.kernels via
this module; `tests/test_kernel_backend.py` pins exact agreement between the
two backends so the swap is always safe.

Routing: a ``repro.ops.plan`` with ``tail='pallas'`` selects
``cpadmm_step_pallas`` on the local backend (core.solvers.make_stepper) and
the same fused cpadmm_tail kernel inside the distributed step
(dist.recovery._tail) — one registry, both backends.

Step math is identical to ista.ista_step / admm.cpadmm_step — only the
execution substrate changes:
  * direct circulant matvec      -> kernels.circulant_matvec (time domain;
                                    CPISTA only — the kernel does not
                                    compile for v5e, so the CPADMM step,
                                    which tail='pallas' runs on the chip,
                                    applies C through its spectrum)
  * threshold + dual update      -> kernels.soft_threshold   (fused VPU)
  * frequency-domain x-update    -> kernels.spectral_pointwise between rffts
  * whole elementwise iter tail  -> kernels.cpadmm_tail (v-update + threshold
                                    + both dual updates, one VMEM pass)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.circulant_matvec.ops import circulant_matvec
from repro.kernels.cpadmm_tail.ops import fused_cpadmm_tail
from repro.kernels.soft_threshold.ops import fused_ista_update
from repro.kernels.spectral_pointwise.ops import spectral_update
from repro.ops.spectral import irfft, rfft

# wire-compressed collectives (plan knob wire_dtype=): the demote-pack /
# promote-unpack pair the distributed transforms fuse around every transpose
# all-to-all — registered here like every kernel substrate so both backends
# share one routing point (dist.fft calls these; re-exported for callers
# that follow the registry rather than the kernel package).
from repro.kernels.wire_pack.ops import (  # noqa: F401  (registry re-export)
    WIRE_DTYPES,
    pack_wire,
    unpack_wire,
)

from .admm import CpadmmConst, CpadmmParams, CpadmmState
from .circulant import PartialCirculant
from .ista import IstaParams, IstaState

Array = jax.Array


def ista_step_pallas(
    op: PartialCirculant, y: Array, state: IstaState, p: IstaParams, *,
    interpret: bool = True,
) -> IstaState:
    """CPISTA iteration on the kernel substrate (Algs. 7-8)."""
    col = op.circ.col
    cx = circulant_matvec(col, state.x, interpret=interpret)
    r = y - jnp.take(cx, op.omega, axis=-1)
    rt = jnp.zeros_like(state.x).at[..., op.omega].set(r)
    grad = circulant_matvec(col, rt, transpose=True, interpret=interpret)
    x_new = fused_ista_update(state.x, p.tau * grad, p.alpha * p.tau, interpret=interpret)
    return IstaState(x=x_new, x_prev=state.x, t_mom=state.t_mom)


def cpadmm_step_pallas(
    op: PartialCirculant,
    const: CpadmmConst,
    state: CpadmmState,
    p: CpadmmParams,
    *,
    interpret: bool = True,
) -> CpadmmState:
    """CPADMM iteration: spectral_pointwise x-update + one fused tail pass.

    Its three parts are the named scopes ``cpadmm.xupdate``, ``cpadmm.cx``
    and ``cpadmm.tail``, as in :func:`repro.core.admm.cpadmm_step`."""
    n = op.n
    with jax.named_scope("cpadmm.xupdate"):
        vm = rfft(state.v + state.mu, n)
        zn = rfft(state.z - state.nu, n)
        x_spec = spectral_update(
            op.circ.spec, const.b_spec.astype(op.circ.spec.dtype), vm, zn,
            p.rho, p.sigma, interpret=interpret,
        )
        x = irfft(x_spec, n)

    with jax.named_scope("cpadmm.cx"):
        cx = op.circ.matvec(x)  # spectral apply: never the direct kernel
    # the entire elementwise tail (v-update, threshold, both duals) is one
    # VMEM-resident kernel pass — kernels/cpadmm_tail
    with jax.named_scope("cpadmm.tail"):
        v, z, mu, nu = fused_cpadmm_tail(
            x, cx, const.d_diag, const.Pty, state.mu, state.nu,
            p.rho, p.alpha / p.sigma, p.tau1, p.tau2, interpret=interpret,
        )
    return CpadmmState(x=x, v=v, z=z, mu=mu, nu=nu)

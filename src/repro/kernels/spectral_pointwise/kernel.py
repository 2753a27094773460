"""Pallas TPU kernel: fused frequency-domain pointwise stage of CPADMM.

The CPADMM x-update is x = B (rho C^T (v + mu) + sigma (z - nu)) with both B
and C^T diagonal in the Fourier basis (paper Sec. 4.3).  Between one forward
and one inverse rFFT, the *entire* update is a pointwise complex program:

    X(f) = b(f) * ( rho * conj(c(f)) * VM(f) + sigma * ZN(f) )

where VM = rfft(v + mu), ZN = rfft(z - nu), c = spec(C), b = spec(B) (real).
Fusing it keeps five operand streams in VMEM for a single pass instead of
launching 4 separate elementwise ops over HBM (the paper's motivation for
merging GPU kernels, Sec. 5).

TPU has no complex dtype in Pallas: complex arrays travel as separate
real/imag planes.  Each plane of the half-spectrum is folded into
``(rows, LANES)`` tiles (``kernels/_tiling``), so any length nf works; a
leading batch axis (B signals through one operator — the batched recovery
pipeline) becomes the inner grid dimension, with the operator spectra c and
b keeping one block index across the batch sweep (fetched once per tile)
while the per-signal streams sweep past them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._tiling import LANES, fold, fold_rows, unfold


def _kernel(
    cr_ref, ci_ref, b_ref, vmr_ref, vmi_ref, znr_ref, zni_ref, rho_ref, sig_ref,
    or_ref, oi_ref,
):
    # conj(c) * vm  (complex multiply with conjugated first operand)
    cr, ci = cr_ref[...], ci_ref[...]
    vr, vi = vmr_ref[...], vmi_ref[...]
    rho, sig = rho_ref[0], sig_ref[0]
    tr = cr * vr + ci * vi  # Re(conj(c) vm)
    ti = cr * vi - ci * vr  # Im(conj(c) vm)
    xr = rho * tr + sig * znr_ref[...]
    xi = rho * ti + sig * zni_ref[...]
    b = b_ref[...]
    or_ref[...] = b * xr
    oi_ref[...] = b * xi


@functools.partial(jax.jit, static_argnames=("interpret",))
def cpadmm_spectral_update(
    c_spec_r: jax.Array,
    c_spec_i: jax.Array,
    b_spec: jax.Array,  # real spectrum of B = (rho |c|^2 + sigma)^{-1}
    vm_r: jax.Array,
    vm_i: jax.Array,
    zn_r: jax.Array,
    zn_i: jax.Array,
    rho: jax.Array,
    sigma: jax.Array,
    *,
    interpret: bool = True,
):
    """-> (X_r, X_i): spectrum of the updated x.

    Operator spectra (c, b) are length-nf vectors; the per-signal streams
    (vm, zn) are (nf,) or batched (B, nf) — one shared operator, B signals.
    """
    nf = c_spec_r.shape[-1]
    rows, rb = fold_rows(nf)
    c_spec_r, c_spec_i, b_spec = (fold(a, rows) for a in (c_spec_r, c_spec_i, b_spec))
    vm_r, vm_i, zn_r, zn_i = (fold(a, rows) for a in (vm_r, vm_i, zn_r, zn_i))
    dt = b_spec.dtype
    rho = jnp.broadcast_to(jnp.asarray(rho, dt), (1,))
    sigma = jnp.broadcast_to(jnp.asarray(sigma, dt), (1,))
    if vm_r.ndim == 3:
        bsz = vm_r.shape[0]
        grid = (rows // rb, bsz)
        # operator spectra: one block index across the inner batch sweep
        tile_op = pl.BlockSpec((rb, LANES), lambda i, b: (i, 0))
        tile_sig = pl.BlockSpec((None, rb, LANES), lambda i, b: (b, i, 0))
        scalar = pl.BlockSpec((1,), lambda i, b: (0,))
    else:
        grid = (rows // rb,)
        tile_op = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
        tile_sig = tile_op
        scalar = pl.BlockSpec((1,), lambda i: (0,))
    out_r, out_i = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[tile_op] * 3 + [tile_sig] * 4 + [scalar, scalar],
        out_specs=[tile_sig, tile_sig],
        out_shape=[jax.ShapeDtypeStruct(vm_r.shape, dt)] * 2,
        interpret=interpret,
    )(c_spec_r, c_spec_i, b_spec, vm_r, vm_i, zn_r, zn_i, rho, sigma)
    return unfold(out_r, nf), unfold(out_i, nf)

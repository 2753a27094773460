"""Pallas TPU kernel: fused CPADMM iteration tail (one VMEM-resident pass).

After the two circulant applies of an iteration (x and Cx), everything left
in Alg. 3 is elementwise:

    v   = d * (pty + rho * (cx - mu))
    z   = eta_gamma(x + nu)
    mu' = mu + tau1 * (v - cx)
    nu' = nu + tau2 * (x - z)

Run as separate XLA ops this is 4 kernel launches reading ~10 operand
streams from HBM; the paper's Sec. 5 motivation for merging GPU kernels
applies unchanged, so here the whole tail is one Pallas pass: six input
streams tiled through VMEM once, four outputs written once, all
intermediates (v, z) living only in registers/VMEM.

Layout mirrors ``spectral_pointwise``: the flattened signal block is folded
into ``(rows, LANES)`` tiles (``kernels/_tiling``), and a leading batch axis
(B signals through one operator) is the inner grid dimension.  The
*operator* streams — d_diag always, pty when it is shared across the batch
(one measurement mask, B signals) — keep one block index across the inner
batch sweep, so each tile of them is fetched once while the per-signal
streams sweep past it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .._tiling import LANES, fold, fold_rows, unfold


def _eta(v, gamma):
    return jnp.sign(v) * jnp.maximum(jnp.abs(v) - gamma, 0.0)


def _kernel(
    d_ref, pty_ref, x_ref, cx_ref, mu_ref, nu_ref,
    rho_ref, gam_ref, t1_ref, t2_ref,
    v_ref, z_ref, mu_out_ref, nu_out_ref,
):
    x, cx = x_ref[...], cx_ref[...]
    mu, nu = mu_ref[...], nu_ref[...]
    v = d_ref[...] * (pty_ref[...] + rho_ref[0] * (cx - mu))
    z = _eta(x + nu, gam_ref[0])
    v_ref[...] = v
    z_ref[...] = z
    mu_out_ref[...] = mu + t1_ref[0] * (v - cx)
    nu_out_ref[...] = nu + t2_ref[0] * (x - z)


@functools.partial(jax.jit, static_argnames=("pty_batched", "interpret"))
def cpadmm_tail_pallas(
    d_diag: jax.Array,  # (L,) operator stream, shared across the batch
    pty: jax.Array,  # (L,) shared or (B, L) per-signal (see pty_batched)
    x: jax.Array,  # (B, L) or (L,) per-signal streams
    cx: jax.Array,
    mu: jax.Array,
    nu: jax.Array,
    rho: jax.Array,
    gamma: jax.Array,  # alpha / sigma
    tau1: jax.Array,
    tau2: jax.Array,
    *,
    pty_batched: bool = False,
    interpret: bool = True,
):
    """-> (v, z, mu', nu') with the shape of ``x``.

    Streams are 1-D (flattened signal block) with an optional leading batch
    axis on the per-signal streams; ``d_diag`` (and ``pty`` unless
    ``pty_batched``) are length-L operator vectors reused across the batch.
    """
    L = x.shape[-1]
    rows, rb = fold_rows(L)
    d_diag, pty = fold(d_diag, rows), fold(pty, rows)
    x, cx, mu, nu = (fold(a, rows) for a in (x, cx, mu, nu))
    dt = x.dtype
    scal = lambda s: jnp.broadcast_to(jnp.asarray(s, dt), (1,))
    rho, gamma, tau1, tau2 = scal(rho), scal(gamma), scal(tau1), scal(tau2)
    if x.ndim == 3:
        bsz = x.shape[0]
        grid = (rows // rb, bsz)
        # operator streams: one block index across the inner batch sweep
        tile_op = pl.BlockSpec((rb, LANES), lambda i, b: (i, 0))
        tile_sig = pl.BlockSpec((None, rb, LANES), lambda i, b: (b, i, 0))
        scalar = pl.BlockSpec((1,), lambda i, b: (0,))
    else:
        grid = (rows // rb,)
        tile_op = pl.BlockSpec((rb, LANES), lambda i: (i, 0))
        tile_sig = tile_op
        scalar = pl.BlockSpec((1,), lambda i: (0,))
    tile_pty = tile_sig if pty_batched else tile_op
    outs = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[tile_op, tile_pty] + [tile_sig] * 4 + [scalar] * 4,
        out_specs=[tile_sig] * 4,
        out_shape=[jax.ShapeDtypeStruct(x.shape, dt)] * 4,
        interpret=interpret,
    )(d_diag, pty, x, cx, mu, nu, rho, gamma, tau1, tau2)
    return tuple(unfold(o, L) for o in outs)

"""Pallas TPU kernel: direct (time-domain) circulant matvec.

TPU adaptation of the paper's CPISTA/CPADMM GPU kernels (Algs. 4-8).  The
GPU version gives each work-item one output row and modular reads of the
shared sensing vector, relying on L2 to de-duplicate traffic.  The TPU
version makes that de-duplication *structural*:

  * grid = (row-tiles, col-tiles); each step owns a (BI, BJ) tile of the
    implicit matrix ``C[i, j] = col[(i - j) mod n]``.
  * the whole doubled vector ``colx = concat(col, col)`` lives in VMEM; the
    kernel slices the length ``BI + BJ - 1`` *window* that generates the
    tile — O(BI + BJ) unique elements instead of O(BI * BJ): the same
    O(n^2) -> O(n) traffic reduction the paper gets from GPU caching
    (DESIGN.md Sec. 2), but guaranteed by the block schedule rather than by
    a cache heuristic.
  * the Toeplitz tile is materialized on-chip from the window with an
    iota-gather and fed to the MXU as a (BI, BJ) x (BJ,) product;
    accumulation over col-tiles happens in the output VMEM block
    (revisited across the inner grid dimension).

Memory budget per step: BI*BJ (tile) + 2n (colx) + BJ (x) + BI (out) floats.
With BI = BJ = 256 and n <= 2^20 this is well under a 16 MiB VMEM (the tile
itself is 256 KiB); for larger n the FFT path takes over (see ops.py).

Neither tile formulation compiles for a TPU v5e with the installed Mosaic
toolchain: the iota-gather (``jnp.take`` of a 1-D VMEM window) is refused
("Only 2D gather is supported"), and so are the BJ unrolled dynamic slices
of ``_tile_via_slices``.  The kernel runs in interpret mode only (CPU
tests); no chip path calls it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

Array = jax.Array

DEFAULT_BLOCK = 128


def _toeplitz_tile_gather(window: Array, bi: int, bj: int) -> Array:
    """tile[a, b] = window[(bj - 1) + a - b]; window has length bi + bj - 1."""
    a = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1)
    return jnp.take(window, (bj - 1) + a - b, axis=0)


def _tile_via_slices(window: Array, bi: int, bj: int) -> Array:
    """Gather-free alternative: bj static slices (columns of the tile)."""
    cols = [
        jax.lax.dynamic_slice_in_dim(window, bj - 1 - b, bi) for b in range(bj)
    ]
    return jnp.stack(cols, axis=1)


def _matvec_kernel(colx_ref, x_ref, o_ref, *, n: int, bi: int, bj: int, transpose: bool, use_gather: bool):
    gi = pl.program_id(0)
    gj = pl.program_id(1)

    # Window generating tile (gi, gj) of C (or C^T).
    #   C   [i, j] = col[(i - j) mod n]        -> base = gi*bi - gj*bj - (bj-1)
    #   C^T [i, j] = col[(j - i) mod n]        -> reversed window direction
    if not transpose:
        base = gi * bi - gj * bj - (bj - 1)
    else:
        # C^T tile[a, b] = col[(gj*bj + b) - (gi*bi + a) mod n]
        #              = colrev window; reuse gather with swapped roles:
        # define window w[t] = col[(gj*bj - gi*bi - (bi - 1) + t) mod n],
        # then tile[a, b] = w[(bi - 1) + b - a] ... we fold by reading the
        # forward window of the *transposed* index arithmetic below.
        base = gj * bj - gi * bi - (bi - 1)

    base = jax.lax.rem(base, n) + n  # positive index into doubled colx
    if not transpose:
        w_len = bi + bj - 1
        window = colx_ref[pl.ds(base, w_len)]
        if use_gather:
            tile = _toeplitz_tile_gather(window, bi, bj)
        else:
            tile = _tile_via_slices(window, bi, bj)
    else:
        w_len = bi + bj - 1
        window = colx_ref[pl.ds(base, w_len)]
        # tile[a, b] = window[(bi - 1) + b - a] == gather with swapped iotas
        a = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 0)
        b = jax.lax.broadcasted_iota(jnp.int32, (bi, bj), 1)
        if use_gather:
            tile = jnp.take(window, (bi - 1) + b - a, axis=0)
        else:
            rows = [
                jax.lax.dynamic_slice_in_dim(window, bi - 1 - aa, bj)
                for aa in range(bi)
            ]
            tile = jnp.stack(rows, axis=0)

    acc = jnp.dot(tile, x_ref[...], preferred_element_type=jnp.float32)

    @pl.when(gj == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += acc.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("transpose", "block", "use_gather", "interpret")
)
def circulant_matvec_pallas(
    col: Array,
    x: Array,
    *,
    transpose: bool = False,
    block: int = DEFAULT_BLOCK,
    use_gather: bool = True,
    interpret: bool = True,
) -> Array:
    """y = C @ x (or C^T @ x) with C[i, j] = col[(i - j) mod n].

    ``n`` must be a multiple of ``block`` (ops.py pads otherwise).
    """
    n = col.shape[-1]
    assert n % block == 0, (n, block)
    assert x.shape[-1] == n
    colx = jnp.concatenate([col, col, col[: 2 * block]])  # headroom for windows
    grid = (n // block, n // block)
    kern = functools.partial(
        _matvec_kernel,
        n=n,
        bi=block,
        bj=block,
        transpose=transpose,
        use_gather=use_gather,
    )
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((colx.shape[0],), lambda i, j: 0),  # resident window pool
            pl.BlockSpec((block,), lambda i, j: j),  # x tile
        ],
        out_specs=pl.BlockSpec((block,), lambda i, j: i),
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=interpret,
    )(colx, x)

"""Shared 2-D tiling for the flat elementwise kernels (v5e-compilable).

Mosaic tiles the last two dimensions of every block in (8, 128) units, and
XLA lays a long 1-D f32 array out in 1024-element tiles, so a 1-D block
(or a ``(1, block)`` batched block) is refused at compile time for the
chip.  The elementwise kernels therefore fold a length-L stream into a
``(rows, LANES)`` view, zero-padded to whole row blocks of ``rb`` rows
(a multiple of 8), and a batched ``(B, L)`` stream into
``(B, rows, LANES)`` with the batch dimension squeezed out of the block.
"""

from __future__ import annotations

import jax.numpy as jnp

LANES = 512
MAX_BLOCK_ROWS = 128  # 128 x 512 f32 = 256 KiB per stream per buffer


def _round_up(a: int, b: int) -> int:
    return -(-a // b) * b


def fold_rows(length: int) -> tuple:
    """-> (rows, rb): padded row count of a length-``length`` stream and the
    rows per block; ``rb`` divides ``rows`` and is a multiple of 8."""
    rows = -(-length // LANES)
    rb = min(MAX_BLOCK_ROWS, _round_up(rows, 8))
    return _round_up(rows, rb), rb


def fold(a, rows: int):
    """(..., L) -> (..., rows, LANES), zero-padded along L."""
    pad = rows * LANES - a.shape[-1]
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a.reshape(a.shape[:-1] + (rows, LANES))


def unfold(a, length: int):
    """Inverse of :func:`fold`: (..., rows, LANES) -> (..., length)."""
    return a.reshape(a.shape[:-2] + (-1,))[..., :length]

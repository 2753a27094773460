"""Deterministic synthetic data: sparse signals, starfield images, token streams.

Everything is generated from explicit PRNG keys so that (a) every test is
reproducible and (b) multi-host pipelines can derive non-overlapping shards
from (seed, host_id, step) without coordination — the restart story never
needs to replay data (DESIGN.md Sec. 4).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


# ---------------------------------------------------------------------------
# Paper Sec. 6: k-sparse Gaussian test signals
# ---------------------------------------------------------------------------


def sparse_signal(
    key: Array, n: int, k: int, batch: Tuple[int, ...] = (), dtype=jnp.float32
) -> Array:
    """x* with exactly k nonzeros, values ~ N(0,1) (paper Sec. 6 setup)."""
    kv, kp = jax.random.split(key)
    vals = jax.random.normal(kv, batch + (n,), dtype)

    def one_mask(k_perm):
        idx = jax.random.permutation(k_perm, n)[:k]
        return jnp.zeros((n,), dtype).at[idx].set(1.0)

    nb = 1
    for b in batch:
        nb *= b
    masks = jax.vmap(one_mask)(jax.random.split(kp, nb)).reshape(batch + (n,))
    return vals * masks


def _mix32(v: Array, k: Array) -> Array:
    """Keyed 32-bit integer hash (xor key, then the lowbias32 finalizer)."""
    v = v ^ k
    v = v * jnp.uint32(0x7FEB352D)
    v = v ^ (v >> 15)
    v = v * jnp.uint32(0x846CA68B)
    return v ^ (v >> 16)


def _keyed_bijection(key: Array, n: int, rounds: int = 6):
    """(F, F_inv): a keyed pseudo-random bijection of [0, n) and its
    inverse, on uint32 arrays, built from elementwise integer ops only.

    A ``rounds``-round Feistel network over the smallest even bit width
    covering n, cycle-walked back into range where n is not a power of
    four (walking F^-1 the same way inverts the walked F).
    """
    bits = max(2, (n - 1).bit_length())
    bits += bits % 2
    if bits > 32:
        raise ValueError(f"n = {n} needs more than 32 index bits")
    half = bits // 2
    low = jnp.uint32((1 << half) - 1)
    keys = jax.random.bits(key, (rounds,), jnp.uint32)

    def fwd(v):
        left, right = v >> half, v & low
        for r in range(rounds):
            left, right = right, left ^ (_mix32(right, keys[r]) & low)
        return (left << half) | right

    def inv(v):
        left, right = v >> half, v & low
        for r in reversed(range(rounds)):
            left, right = right ^ (_mix32(left, keys[r]) & low), left
        return (left << half) | right

    def walked(step):
        def apply(v):
            v = step(v)
            if n < (1 << bits):
                v = jax.lax.while_loop(
                    lambda v: jnp.any(v >= n),
                    lambda v: jnp.where(v >= n, step(v), v),
                    v,
                )
            return v

        return apply

    return walked(fwd), walked(inv)


def random_subset_mask(key: Array, n: int, k: int) -> Array:
    """Indicator (bool, length n) of a pseudo-random subset of exactly k
    indices, drawn without a sort.

    ``jax.random.permutation`` (behind :func:`sparse_signal` and
    ``core.circulant.random_omega``) lowers to sorts, and the TPU compiler
    takes tens of seconds per sort at n >= 2^18; a scatter or
    ``jnp.nonzero`` is nearly as slow to compile.  Here index j is kept iff
    ``F(j) < k`` for the keyed bijection F of :func:`_keyed_bijection`, so
    exactly k indices pass and every step is elementwise.
    :func:`random_subset_indices` lists the same subset.
    """
    fwd, _ = _keyed_bijection(key, n)
    return fwd(jnp.arange(n, dtype=jnp.uint32)) < k


def random_subset_indices(key: Array, n: int, k: int) -> Array:
    """The subset of :func:`random_subset_mask` (same key) as k int32
    indices, in pseudo-random order: ``F^-1(0 .. k-1)``, again sort-free."""
    _, inv = _keyed_bijection(key, n)
    return inv(jnp.arange(k, dtype=jnp.uint32)).astype(jnp.int32)


def paper_regime(n: int) -> Tuple[int, int]:
    """Paper Sec. 6: m = n/2 measurements, k ~= n/10 nonzeros."""
    return n // 2, max(1, n // 10)


# ---------------------------------------------------------------------------
# Paper Sec. 7: synthetic astronomical starfield (Abell-2744 stand-in)
# ---------------------------------------------------------------------------


def starfield(
    key: Array,
    h: int = 256,
    w: int = 256,
    density: float = 0.10,
    n_blobs: int = 12,
    dtype=jnp.float32,
) -> Array:
    """Sparse night-sky image: point sources (~``density`` of pixels lit,
    matching the paper's "sparsity about 10% of the signal size") plus a few
    soft elliptical blobs standing in for cluster galaxies.  Intensities in
    [0, 1]."""
    k_pts, k_int, k_blob = jax.random.split(key, 3)

    # Point sources.
    lit = jax.random.bernoulli(k_pts, density, (h, w))
    intensity = jax.random.uniform(k_int, (h, w), dtype, 0.2, 1.0)
    img = jnp.where(lit, intensity, 0.0)

    # Extended sources: sum of anisotropic Gaussians.
    yy = jnp.arange(h, dtype=dtype)[:, None]
    xx = jnp.arange(w, dtype=dtype)[None, :]
    params = jax.random.uniform(k_blob, (n_blobs, 5), dtype)  # cy cx sy sx amp

    def blob(img, p):
        cy, cx = p[0] * h, p[1] * w
        sy = 1.5 + p[2] * (h / 40.0)
        sx = 1.5 + p[3] * (w / 40.0)
        amp = 0.3 + 0.7 * p[4]
        g = amp * jnp.exp(-(((yy - cy) / sy) ** 2 + ((xx - cx) / sx) ** 2))
        return img + g, None

    img, _ = jax.lax.scan(blob, img, params)
    img = jnp.clip(img, 0.0, 1.0)
    # Kill sub-perceptual blob tails so the image stays genuinely sparse
    # (the paper's premise: most night-sky pixels are black).
    return jnp.where(img < 0.02, 0.0, img)


def extended_emission(
    key: Array,
    h: int = 256,
    w: int = 256,
    n_sources: int = 3,
    background: float = 0.05,
    dtype=jnp.float32,
) -> Array:
    """Piecewise-constant extended-emission map (Herschel-style dust/cloud
    field): ``n_sources`` flat-topped disks of random center/radius/intensity
    over a faint uniform background.  The complement of :func:`starfield` —
    almost nowhere zero but gradient-sparse, which is the regime where the
    TV prior (``repro.ops.prox.TVProx``) beats the paper's l1 threshold
    (``repro.core.mapmaking`` / tests pin the gap).  Intensities in [0, 1].
    """
    yy = jnp.arange(h, dtype=dtype)[:, None]
    xx = jnp.arange(w, dtype=dtype)[None, :]
    params = jax.random.uniform(key, (n_sources, 4), dtype)  # cy cx r amp

    def disk(img, p):
        cy, cx = p[0] * h, p[1] * w
        r = (0.10 + 0.18 * p[2]) * min(h, w)
        amp = 0.4 + 0.6 * p[3]
        inside = (yy - cy) ** 2 + (xx - cx) ** 2 <= r * r
        return jnp.where(inside, jnp.maximum(img, amp), img), None

    img, _ = jax.lax.scan(disk, jnp.full((h, w), background, dtype), params)
    return jnp.clip(img, 0.0, 1.0)


# ---------------------------------------------------------------------------
# LM substrate: deterministic token streams
# ---------------------------------------------------------------------------


def token_batch(
    seed: int, step: int, host: int, batch: int, seq_len: int, vocab: int
) -> Array:
    """(batch, seq_len+1) int32 tokens, unique per (seed, step, host).

    A Zipf-ish marginal (mixture of a low-id head and a uniform tail) so the
    loss curve is non-degenerate; fully deterministic => a restarted run
    consumes exactly the missed batches and no others."""
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(seed), step), host)
    k1, k2, k3 = jax.random.split(key, 3)
    head = jax.random.randint(k1, (batch, seq_len + 1), 0, max(2, vocab // 64))
    tail = jax.random.randint(k2, (batch, seq_len + 1), 0, vocab)
    pick_head = jax.random.bernoulli(k3, 0.8, (batch, seq_len + 1))
    return jnp.where(pick_head, head, tail).astype(jnp.int32)

"""Device-side generators of the benchmark's inputs, all from one PRNG key.

Copied from the recovery system's synthetic-data module (its sort-free
subset draw, starfield frames) and its operator factories
(Romberg random-phase sensing, the paper's moving-average blur), so that
the inputs, and the reference that checks the program, stay fixed while
the program changes.  Nothing here sorts: ``jax.random.permutation``
compiles sorts for tens of seconds on a TPU at n >= 2^18, so subsets come
from a keyed Feistel bijection, as in the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def key_from_seed(seed: int):
    """A PRNG key from any non-negative integer seed (also > 2^32)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    high = seed >> 32
    while high:
        key = jax.random.fold_in(key, high & 0xFFFFFFFF)
        high >>= 32
    return key


def _mix32(v, k):
    v = v ^ k
    v = v * jnp.uint32(0x7FEB352D)
    v = v ^ (v >> 15)
    v = v * jnp.uint32(0x846CA68B)
    return v ^ (v >> 16)


def _bijection(key, n: int, rounds: int = 6):
    """(F, F^-1): a keyed pseudo-random bijection of [0, n) on uint32."""
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
                v = jax.lax.while_loop(lambda v: jnp.any(v >= n),
                                       lambda v: jnp.where(v >= n, step(v), v), v)
            return v
        return apply

    return walked(fwd), walked(inv)


def subset_indices(key, n: int, k: int):
    """Exactly k of the n indices [0, n), as int32, in no particular order."""
    _, inv = _bijection(key, n)
    return inv(jnp.arange(k, dtype=jnp.uint32)).astype(jnp.int32)


def starfield(key, h: int, w: int, density: float, n_blobs: int):
    """Sparse night-sky frame in [0, 1]: point sources lit with
    probability ``density`` plus ``n_blobs`` soft elliptical galaxies,
    tails below 0.02 cut to black (the Sec. 7 stand-in for Abell 2744)."""
    k_pts, k_int, k_blob = jax.random.split(key, 3)
    lit = jax.random.bernoulli(k_pts, density, (h, w))
    intensity = jax.random.uniform(k_int, (h, w), jnp.float32, 0.2, 1.0)
    img = jnp.where(lit, intensity, 0.0)
    yy = jnp.arange(h, dtype=jnp.float32)[:, None]
    xx = jnp.arange(w, dtype=jnp.float32)[None, :]
    params = jax.random.uniform(k_blob, (n_blobs, 5), jnp.float32)

    def blob(img, p):
        cy, cx = p[0] * h, p[1] * w
        sy, sx = 1.5 + p[2] * (h / 40.0), 1.5 + p[3] * (w / 40.0)
        amp = 0.3 + 0.7 * p[4]
        return img + amp * jnp.exp(-(((yy - cy) / sy) ** 2 + ((xx - cx) / sx) ** 2)), None

    img, _ = jax.lax.scan(blob, img, params)
    img = jnp.clip(img, 0.0, 1.0)
    return jnp.where(img < 0.02, 0.0, img)


def romberg_col(key, n: int):
    """First column of a Romberg random-convolution circulant: unit-modulus
    half spectrum with uniform random phases, real DC and Nyquist bins."""
    phase = jax.random.uniform(key, (n // 2 + 1,), jnp.float32) * (2 * jnp.pi)
    spec = jnp.exp(1j * phase.astype(jnp.complex64))
    spec = spec.at[0].set(1.0)
    if n % 2 == 0:
        spec = spec.at[-1].set(1.0)
    return jnp.fft.irfft(spec, n=n)


def moving_average_col(n: int, order: int):
    """First column of the paper's order-L raster blur (Sec. 7): first row
    [1/L] * L then zeros, so col[i] = row[-i mod n]."""
    row = jnp.zeros((n,), jnp.float32).at[:order].set(1.0 / order)
    return jnp.roll(row[::-1], 1)

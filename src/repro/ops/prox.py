"""Pluggable proximal operators — the prior as a first-class plan knob.

The paper's solvers (CPISTA Alg. 1, CPADMM Alg. 3) hardwire the
identity-basis l1 prior: every z-update is ``eta_gamma(x + u)`` with
``eta_gamma`` the soft threshold of Eq. 4.  The astronomy workloads the
paper targets want more — *Compressed Sensing in Astronomy* (Bobin/Starck)
reconstructs under TV and wavelet analysis priors, and astronomical images
are nonnegative.  This module turns the prior into a value: a ``Prox``
object with ``apply(x, gamma)`` computing

    prox_{gamma * R}(x) = argmin_z  0.5 * ||z - x||^2 + gamma * R(z)

that threads through ``PlanConfig(prox=)``, the solver steppers, the tuner
and the serve bucket keys.  Contract:

* ``apply(x, gamma)`` acts on the trailing axis (flat signal of length n)
  and broadcasts over any leading batch axes — batched recovery applies the
  prior per-signal with one call.
* ``tag`` is a stable human-readable id; it parameterizes
  ``PlanConfig.describe()`` so serve buckets with different priors never
  share an engine, and distinct hyper-parameters yield distinct tags.
* ``elementwise`` marks proxes that act coordinate-wise.  Elementwise
  proxes can run *inside* a shard_map on sharded iterate blocks;
  non-elementwise proxes (TV, wavelet) need the whole signal and run at
  the global jit level where GSPMD partitions them.
* ``L1Prox`` is the bit-exact compatibility default: its ``apply`` is the
  same jnp expression as ``core.soft_threshold.soft_threshold``, so the
  refactor changes no numbers, and the fused Pallas tails
  (``kernels/soft_threshold``, ``kernels/cpadmm_tail``) stay reachable
  exactly when ``is_l1(prox)``.
* TV and wavelet additionally expose an ``analysis_op`` /
  ``analysis_rmatvec`` pair (the D and D^T of the analysis form
  ``R(z) = ||D z||_1``) for analysis-form ADMM splittings and diagnostics.

Everything here is plain jax — no imports from repro.core / repro.dist —
so any layer can depend on it without cycles.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array


def _soft(x: Array, gamma) -> Array:
    # Same expression as core.soft_threshold.soft_threshold — kept inline so
    # this module stays dependency-free while L1Prox remains bitwise equal.
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - gamma, 0.0)


class Prox:
    """Protocol/base for proximal operators (see module docstring).

    Subclasses are frozen dataclasses with only hashable fields so a Prox
    can sit inside the frozen ``PlanConfig`` and the tuner's group keys.
    """

    kind: ClassVar[str]
    elementwise: ClassVar[bool]

    @property
    def tag(self) -> str:
        raise NotImplementedError

    def apply(self, x: Array, gamma) -> Array:
        raise NotImplementedError

    def to_dict(self) -> Dict[str, Any]:
        d = {"kind": self.kind}
        d.update(
            {
                f.name: (list(v) if isinstance(v := getattr(self, f.name), tuple) else v)
                for f in dataclasses.fields(self)  # type: ignore[arg-type]
            }
        )
        return d


@dataclasses.dataclass(frozen=True)
class L1Prox(Prox):
    """Identity-basis l1 soft threshold (paper Eq. 4) — the compat default."""

    kind: ClassVar[str] = "l1"
    elementwise: ClassVar[bool] = True

    @property
    def tag(self) -> str:
        return "l1"

    def apply(self, x: Array, gamma) -> Array:
        return _soft(x, gamma)


@dataclasses.dataclass(frozen=True)
class NonNegL1Prox(Prox):
    """l1 + nonnegativity: prox is a one-sided shrink, max(x - gamma, 0).

    Astronomy images are photon counts — the positivity constraint is free
    regularization (Bobin/Starck Sec. 5).
    """

    kind: ClassVar[str] = "nonneg-l1"
    elementwise: ClassVar[bool] = True

    @property
    def tag(self) -> str:
        return "nonneg-l1"

    def apply(self, x: Array, gamma) -> Array:
        return jnp.maximum(x - gamma, 0.0)


@dataclasses.dataclass(frozen=True)
class TVProx(Prox):
    """Anisotropic 2-D total variation via Chambolle's dual projection.

    ``R(z) = ||Dv z||_1 + ||Dh z||_1`` with periodic (circulant) forward
    differences — the same wrap-around convention as the repo's circulant
    operators, so the analysis pair stays mesh-shardable (rolls lower to
    collective-permutes under GSPMD).  The prox solves the dual

        min_{||p||_inf <= gamma}  0.5 * ||x - D^T p||^2

    by ``iters`` projected-gradient steps with the safe step 1/8
    (||D||^2 <= 8 for the periodic 2-D difference operator); the primal is
    recovered as ``z = x - D^T p``.  A handful of inner iterations is the
    standard inexact-prox regime (Chambolle 2004; Beck/Teboulle FISTA-TV).
    """

    shape: Tuple[int, int]
    iters: int = 10
    kind: ClassVar[str] = "tv"
    elementwise: ClassVar[bool] = False

    def __post_init__(self):
        h, w = self.shape
        if not (h > 0 and w > 0):
            raise ValueError(f"TVProx shape must be positive; got {self.shape}")
        if self.iters <= 0:
            raise ValueError(f"TVProx iters must be positive; got {self.iters}")
        object.__setattr__(self, "shape", (int(h), int(w)))

    @property
    def tag(self) -> str:
        h, w = self.shape
        return f"tv[{h}x{w},it{self.iters}]"

    def _check(self, x: Array) -> None:
        h, w = self.shape
        if x.shape[-1] != h * w:
            raise ValueError(
                f"TVProx expects trailing axis of length h*w = {h * w} "
                f"(shape={self.shape}); got {x.shape[-1]}"
            )

    def apply(self, x: Array, gamma) -> Array:
        self._check(x)
        h, w = self.shape
        img = x.reshape(x.shape[:-1] + (h, w))

        def dv(z):
            return jnp.roll(z, -1, axis=-2) - z

        def dh(z):
            return jnp.roll(z, -1, axis=-1) - z

        def dvt(p):
            return jnp.roll(p, 1, axis=-2) - p

        def dht(p):
            return jnp.roll(p, 1, axis=-1) - p

        def body(_, carry):
            p1, p2 = carry
            z = img - (dvt(p1) + dht(p2))
            p1 = jnp.clip(p1 + 0.125 * dv(z), -gamma, gamma)
            p2 = jnp.clip(p2 + 0.125 * dh(z), -gamma, gamma)
            return p1, p2

        zero = jnp.zeros_like(img)
        p1, p2 = lax.fori_loop(0, self.iters, body, (zero, zero))
        out = img - (dvt(p1) + dht(p2))
        return out.reshape(x.shape)

    def analysis_op(self, x: Array) -> Array:
        """D x: stacked periodic differences, (..., n) -> (..., 2n)."""
        self._check(x)
        h, w = self.shape
        img = x.reshape(x.shape[:-1] + (h, w))
        dv = jnp.roll(img, -1, axis=-2) - img
        dh = jnp.roll(img, -1, axis=-1) - img
        flat = x.shape[:-1] + (h * w,)
        return jnp.concatenate([dv.reshape(flat), dh.reshape(flat)], axis=-1)

    def analysis_rmatvec(self, c: Array) -> Array:
        """D^T c: adjoint of ``analysis_op``, (..., 2n) -> (..., n)."""
        h, w = self.shape
        n = h * w
        if c.shape[-1] != 2 * n:
            raise ValueError(f"TVProx analysis_rmatvec expects trailing axis 2n = {2 * n}; got {c.shape[-1]}")
        grid = c.shape[:-1] + (h, w)
        p1 = c[..., :n].reshape(grid)
        p2 = c[..., n:].reshape(grid)
        out = (jnp.roll(p1, 1, axis=-2) - p1) + (jnp.roll(p2, 1, axis=-1) - p2)
        return out.reshape(c.shape[:-1] + (n,))


_SQRT2 = math.sqrt(2.0)
_SQRT3 = math.sqrt(3.0)
_WAVELET_FILTERS: Dict[str, Tuple[float, ...]] = {
    "haar": (1.0 / _SQRT2, 1.0 / _SQRT2),
    "db4": (
        (1.0 + _SQRT3) / (4.0 * _SQRT2),
        (3.0 + _SQRT3) / (4.0 * _SQRT2),
        (3.0 - _SQRT3) / (4.0 * _SQRT2),
        (1.0 - _SQRT3) / (4.0 * _SQRT2),
    ),
}


@dataclasses.dataclass(frozen=True)
class WaveletProx(Prox):
    """Soft threshold in an orthogonal periodized wavelet basis.

    ``prox_{gamma * ||W.||_1}(x) = W^T eta_gamma(W x)`` — exact for
    orthonormal W.  W is a ``levels``-deep periodized DWT with Haar or
    Daubechies-4 filters; only detail bands are thresholded (the coarsest
    approximation carries the image's DC/large-scale flux and is kept).
    """

    levels: int = 2
    wavelet: str = "haar"
    kind: ClassVar[str] = "wavelet"
    elementwise: ClassVar[bool] = False

    def __post_init__(self):
        if self.levels <= 0:
            raise ValueError(f"WaveletProx levels must be positive; got {self.levels}")
        if self.wavelet not in _WAVELET_FILTERS:
            raise ValueError(
                f"unknown wavelet {self.wavelet!r}; available: {sorted(_WAVELET_FILTERS)}"
            )

    @property
    def tag(self) -> str:
        return f"wavelet[{self.wavelet},L{self.levels}]"

    def _filters(self, dtype) -> Tuple[Array, Array]:
        h = jnp.asarray(_WAVELET_FILTERS[self.wavelet], dtype=dtype)
        length = h.shape[0]
        # QMF pair: g[k] = (-1)^k h[L-1-k]
        signs = jnp.asarray([(-1.0) ** k for k in range(length)], dtype=dtype)
        g = signs * h[::-1]
        return h, g

    def _check(self, n: int) -> None:
        step = 2**self.levels
        flen = len(_WAVELET_FILTERS[self.wavelet])
        if n % step != 0 or n // step < flen:
            raise ValueError(
                f"WaveletProx(levels={self.levels}, wavelet={self.wavelet!r}) needs the "
                f"signal length divisible by 2^levels = {step} with at least {flen} "
                f"coefficients at the coarsest level; got n={n}"
            )

    @staticmethod
    def _down(a: Array, f: Array) -> Array:
        # a'[i] = sum_m f[m] a[(2i+m) mod N]
        acc = f[0] * a
        for m in range(1, f.shape[0]):
            acc = acc + f[m] * jnp.roll(a, -m, axis=-1)
        return acc[..., ::2]

    @staticmethod
    def _up(c: Array, f: Array, n: int) -> Array:
        # adjoint of _down: scatter to even slots then correlate with +m rolls
        up = jnp.zeros(c.shape[:-1] + (n,), dtype=c.dtype)
        up = up.at[..., ::2].set(c)
        acc = f[0] * up
        for m in range(1, f.shape[0]):
            acc = acc + f[m] * jnp.roll(up, m, axis=-1)
        return acc

    def _decompose(self, x: Array):
        h, g = self._filters(x.dtype)
        a = x
        details = []
        for _ in range(self.levels):
            details.append(self._down(a, g))
            a = self._down(a, h)
        return a, details, (h, g)

    def _reconstruct(self, a: Array, details, filters) -> Array:
        h, g = filters
        for d in reversed(details):
            a = self._up(a, h, 2 * a.shape[-1]) + self._up(d, g, 2 * a.shape[-1])
        return a

    def apply(self, x: Array, gamma) -> Array:
        self._check(x.shape[-1])
        a, details, filters = self._decompose(x)
        details = [_soft(d, gamma) for d in details]
        return self._reconstruct(a, details, filters)

    def analysis_op(self, x: Array) -> Array:
        """W x: concatenated [d_1 | d_2 | ... | d_L | a_L], same length as x."""
        self._check(x.shape[-1])
        a, details, _ = self._decompose(x)
        return jnp.concatenate(details + [a], axis=-1)

    def analysis_rmatvec(self, c: Array) -> Array:
        """W^T c — for orthonormal W also the inverse transform."""
        n = c.shape[-1]
        self._check(n)
        lengths = [n // 2 ** (lvl + 1) for lvl in range(self.levels)]
        details, off = [], 0
        for ln in lengths:
            details.append(c[..., off : off + ln])
            off += ln
        a = c[..., off:]
        h, g = self._filters(c.dtype)
        return self._reconstruct(a, details, (h, g))


PROX_KINDS: Dict[str, type] = {
    L1Prox.kind: L1Prox,
    NonNegL1Prox.kind: NonNegL1Prox,
    TVProx.kind: TVProx,
    WaveletProx.kind: WaveletProx,
}


def is_l1(prox) -> bool:
    """True when the prior is the identity-basis soft threshold — i.e. the
    fused Pallas tails (`kernels/soft_threshold`, `kernels/cpadmm_tail`)
    compute exactly this prox and stay eligible."""
    return prox is None or type(prox) is L1Prox


def check_tail(tail: str, prox) -> None:
    """Reject ``tail='pallas'`` with a non-l1 prior.

    The fused CPADMM tail kernel bakes in the l1 soft threshold; running
    another prior under a plan that asked for the kernel would have to
    swap in the jnp tail behind the caller's back."""
    if tail == "pallas" and not is_l1(prox):
        raise ValueError(
            f"tail='pallas' runs the fused l1 CPADMM tail kernel and cannot "
            f"apply the prior {getattr(prox, 'tag', prox)!r}; use "
            f"tail='jnp' for non-l1 priors"
        )


def is_elementwise(prox) -> bool:
    """True when the prox acts coordinate-wise (safe inside a shard_map)."""
    return prox is None or bool(getattr(prox, "elementwise", False))


def prox_to_dict(prox) -> Dict[str, Any]:
    if prox is None:
        return None  # type: ignore[return-value]
    return prox.to_dict()


def prox_from_dict(d) -> Prox:
    """Rebuild a Prox from its ``to_dict`` form (PlanConfig JSON round-trip)."""
    if d is None:
        return None  # type: ignore[return-value]
    if isinstance(d, Prox):
        return d
    spec = dict(d)
    kind = spec.pop("kind", None)
    cls = PROX_KINDS.get(kind)
    if cls is None:
        raise ValueError(f"unknown prox kind {kind!r}; available: {sorted(PROX_KINDS)}")
    if "shape" in spec and isinstance(spec["shape"], list):
        spec["shape"] = tuple(spec["shape"])
    return cls(**spec)

"""Shared spectral helpers: the one home for rfft bookkeeping.

Every layer of the stack manipulates spectra of *real* signals, so the
half-spectrum (rfft) representation and its Hermitian bookkeeping show up
everywhere: the single-device circulant algebra (``repro.core.circulant``
stores eigenvalues as ``rfft(first column)``), the CPADMM inner inverse
(``repro.core.admm``), and the distributed four-step transforms
(``repro.dist.fft`` keeps ``n2//2 + 1`` columns on the wire).  These
helpers used to be copied privately between ``core/circulant.py`` and
``dist/fft.py``; they live here once, dependency-free (jax only), so both
import the same definitions.

Conventions: 1-D transforms act on the trailing axis and broadcast over
leading batch axes; ``n2``/``p`` in the half-spectrum helpers refer to the
four-step layout's column count and mesh size (see ``repro.dist.fft``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


# --------------------------------------------------------------------------
# trailing-axis real FFT pair (the core circulant algebra's workhorses)
# --------------------------------------------------------------------------


def rfft(x: Array, n: int) -> Array:
    """Length-``n`` real FFT along the trailing axis (scope ``transform``
    in the compiled program's ``op_name`` metadata, as :func:`irfft`)."""
    with jax.named_scope("transform"):
        return jnp.fft.rfft(x, n=n, axis=-1)


def irfft(x: Array, n: int) -> Array:
    """Length-``n`` inverse real FFT along the trailing axis, computed as the
    one-sided inverse DFT of the half spectrum ``X[0..nf)``:

        irfft(X, n) = Re( ifft_n( pad(w * X, n) ) ),
        w_k = 1 for k = 0 and, when n is even, k = n/2; else w_k = 2.

    Each kept bin stands for itself and its Hermitian mirror
    ``X[n - k] = conj(X[k])``, whose sum is twice the real part; DC and the
    Nyquist bin have no mirror, and the real part drops their imaginary
    parts as ``jnp.fft.irfft`` does.  The TPU compiler lowers
    ``jnp.fft.irfft`` to a full-length complex FFT in front of which it
    builds the mirrored half with two lane-axis ``reverse`` ops (real and
    imaginary planes); on a v5e these run far below the memory roofline.
    The one-sided form runs the same complex FFT on zeros in place of the
    mirrored half, so it needs no reverse.  As ``jnp.fft.irfft``, the input
    is cropped to ``n//2 + 1`` bins when longer and zero-padded when
    shorter; complex64 in gives float32 out.
    """
    with jax.named_scope("transform"):
        nf = min(x.shape[-1], n // 2 + 1)
        x = x[..., :nf]
        k = jax.lax.broadcasted_iota(jnp.int32, (nf,), 0)
        unpaired = k == 0
        if n % 2 == 0:
            unpaired |= k == n // 2
        w = jnp.where(unpaired, 1.0, 2.0).astype(jnp.finfo(x.dtype).dtype)
        pads = [(0, 0)] * (x.ndim - 1) + [(0, n - nf)]
        return jnp.real(jnp.fft.ifft(jnp.pad(w * x, pads), axis=-1))


def apply_spectrum(spec: Array, x: Array, n: int) -> Array:
    """``irfft(spec * rfft(x))`` — one circulant application by the
    convolution theorem (paper Sec. 4's C = F^H diag(spec) F identity)."""
    return irfft(spec * rfft(x, n), n)


def gram_inverse_spectrum(spec: Array, rho, sigma) -> Array:
    """Spectrum of ``(rho C^T C + sigma I)^{-1}`` from the spectrum of C.

    Paper Alg. 3 line 2: ``spec(rho C^T C + sigma I) = rho |spec|^2 + sigma``
    (real, positive), so the inverse is the pointwise reciprocal — the
    O(n log n) inversion that replaces the dense O(n^3) one.  Works on any
    spectrum layout (full, half, or the distributed column-sharded block):
    the identity is pointwise.
    """
    return (1.0 / (rho * jnp.abs(spec) ** 2 + sigma)).astype(spec.dtype)


# --------------------------------------------------------------------------
# half-spectrum (rfft) bookkeeping for the four-step (n1, n2) layout
# --------------------------------------------------------------------------


def full_from_half(spec_h: Array, n: int) -> Array:
    """Flat half spectrum (..., n//2 + 1) -> full flat DFT (..., n).

    Hermitian symmetry of a real signal's DFT, ``X[n - k] = conj(X[k])``,
    reconstructs the discarded bins.  This is pure bookkeeping (a conjugate
    flip + concatenate) — no transform runs, which is what lets a circulant's
    stored half spectrum be re-laid-out for any backend without a time-domain
    round trip (see :func:`spectrum_layout_2d`).  The flat case is
    :func:`half_to_full` on a single-row (n1 = 1) layout — one home for the
    symmetry math.
    """
    return half_to_full(spec_h[..., None, :], n)[..., 0, :]


def spectrum_layout_2d(
    spec_h: Array, n1: int, n2: int, *, rfft: bool = False, p: int = 1
) -> Array:
    """Flat half spectrum -> the four-step ``(n1, n2)`` spectrum layout.

    The four-step transform of :mod:`repro.dist.fft` produces
    ``F[k1, k2] = X[n2*k1 + k2]``, so the layout is a plain row-major reshape
    of the full flat DFT — meaning a circulant whose spectrum is already
    known (e.g. the composed sensing+blur operator ``spec(C)·spec(B)`` of
    paper Sec. 7) lowers onto the mesh with *zero* transforms: no irfft back
    to the first column, no distributed FFT of it.  ``rfft=True`` returns
    the half layout the rfft solver path consumes — the kept columns
    ``k2 in [0, n2//2]`` zero-padded to a multiple of the mesh size ``p``
    (matching ``rfft2_local``'s output exactly).
    """
    n = n1 * n2
    F = full_from_half(spec_h, n).reshape(spec_h.shape[:-1] + (n1, n2))
    if not rfft:
        return F
    nf, nf_pad = rfft_len(n2), padded_rfft_len(n2, p)
    pads = [(0, 0)] * F.ndim
    pads[-1] = (0, nf_pad - nf)
    return jnp.pad(F[..., :nf], pads)


def rfft_len(n2: int) -> int:
    """Kept columns of the half spectrum: k2 in [0, n2//2]."""
    return n2 // 2 + 1


def padded_rfft_len(n2: int, p: int) -> int:
    """Kept columns zero-padded up to a multiple of the mesh size ``p`` so
    the transpose-collective can split them evenly on any device count."""
    nf = rfft_len(n2)
    return -(-nf // p) * p


def half_to_full(Fh: Array, n2: int) -> Array:
    """Half-spectrum layout (..., n1, >=nf) -> full spectrum (..., n1, n2).

    The discarded columns follow from Hermitian symmetry of the flat DFT,
    ``X[n - k] = conj(X[k])``: with ``k = n2*k1 + k2`` that reads

        F[k1, k2] = conj(F[n1 - 1 - k1, n2 - k2])    for k2 in [nf, n2).

    Verification/bridging helper — solvers never materialize the full half.
    """
    nf = rfft_len(n2)
    Fh = Fh[..., :nf]
    tail = jnp.flip(jnp.conj(Fh[..., 1 : n2 - nf + 1]), axis=(-2, -1))
    return jnp.concatenate([Fh, tail], axis=-1)

"""ADMM for LASSO: dense baseline (paper Alg. 2) and circulant CPADMM (Alg. 3).

Dense ADMM (PADMM baseline)
    Pays the O(n^3) inverse of (A^T A + rho I) up front and stores the n x n
    inverse — the exact cost profile the paper measures in Figs. 3-4.

Circulant ADMM (CPADMM)
    For A = P C (partial circulant) the splitting of Yin et al. [25] makes
    both inner inverses structured:
        B = (rho C^T C + sigma I)^{-1}   — circulant: reciprocal spectrum,
                                           O(n log n) instead of O(n^3)
        D = (P^T P + rho I)^{-1}         — diagonal: 1/(1+rho) on Omega,
                                           1/rho elsewhere
    Each iteration is then 3 circulant matvecs (C^T v, C x twice — we reuse
    one) + elementwise work: exactly the paper's three GPU kernels
    (Algs. 4, 5, 6), here expressed in the FFT domain.

We implement the *scaled-dual* form of Alg. 3, which is algebraically the
paper's update with its trailing ``v <- v + mu`` folding (see the derivation
note in DESIGN.md Sec. 1 / tests/test_solvers.py::test_cpadmm_matches_paper_form).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.ops.spectral import apply_spectrum

from .circulant import DenseOperator, PartialCirculant
from .soft_threshold import soft_threshold

Array = jax.Array


# ---------------------------------------------------------------------------
# Dense ADMM — paper Alg. 2 (the PADMM baseline)
# ---------------------------------------------------------------------------


class DenseAdmmState(NamedTuple):
    x: Array
    z: Array
    u: Array


class DenseAdmmConst(NamedTuple):
    """Per-problem constants: the O(n^2)-memory inverse the paper measures."""

    B: Array  # (n, n) = (A^T A + rho I)^{-1}
    Aty: Array  # (n,) = A^T y


def dense_admm_setup(op: DenseOperator, y: Array, rho: float) -> DenseAdmmConst:
    """Alg. 2 line 2: the O(n^3) inversion (timed separately as PADMM-I)."""
    A = op.to_dense()
    n = A.shape[1]
    gram = A.T @ A + rho * jnp.eye(n, dtype=A.dtype)
    B = jnp.linalg.inv(gram)
    return DenseAdmmConst(B=B, Aty=op.rmatvec(y))


def dense_admm_init(op, y: Array) -> DenseAdmmState:
    batch = y.shape[:-1]
    z = jnp.zeros(batch + (op.n,), y.dtype)
    return DenseAdmmState(x=z, z=z, u=z)


def dense_admm_step(
    const: DenseAdmmConst, state: DenseAdmmState, alpha: float, rho: float, prox=None
) -> DenseAdmmState:
    """Alg. 2 lines 4-6 (``prox=None`` = the paper's soft threshold)."""
    x = jnp.einsum(
        "nk,...k->...n", const.B, const.Aty + rho * (state.z - state.u)
    )
    if prox is None:
        z = soft_threshold(x + state.u, alpha / rho)
    else:
        z = prox.apply(x + state.u, alpha / rho)
    u = state.u + x - z
    return DenseAdmmState(x=x, z=z, u=u)


# ---------------------------------------------------------------------------
# Circulant ADMM — paper Alg. 3 (CPADMM)
# ---------------------------------------------------------------------------


class CpadmmState(NamedTuple):
    x: Array  # primal estimate (the recovered signal)
    v: Array  # primal splitting variable, v ~= C x
    z: Array  # l1 auxiliary
    mu: Array  # scaled dual for v = C x
    nu: Array  # scaled dual for z = x


class CpadmmConst(NamedTuple):
    b_spec: Array  # rfft spectrum of B = (rho C^T C + sigma I)^{-1}
    d_diag: Array  # (n,) diagonal of D = (P^T P + rho I)^{-1}
    Pty: Array  # (..., n) = P^T y scattered measurements


class CpadmmParams(NamedTuple):
    alpha: Array
    rho: Array
    sigma: Array
    tau1: Array  # dual step, in (0, (sqrt(5)+1)/2) per paper Sec. 4.3
    tau2: Array


def cpadmm_setup(op: PartialCirculant, y: Array, p: CpadmmParams) -> CpadmmConst:
    """Alg. 3 line 2 — the FFT-based O(n log n) inversion.

    spec(rho C^T C + sigma I) = rho |spec(C)|^2 + sigma  (real, positive), so
    B's spectrum is its pointwise reciprocal (the operator's gram-inverse
    capability; one definition in repro.ops.spectral shared with the
    distributed plan layer).  D is diagonal by inspection.
    """
    b_spec = op.gram_inverse_spectrum(p.rho, p.sigma)
    d_diag = jnp.full((op.n,), 1.0 / p.rho, dtype=y.dtype)
    d_diag = d_diag.at[op.omega].set(1.0 / (1.0 + p.rho))
    return CpadmmConst(b_spec=b_spec, d_diag=d_diag, Pty=op.project_back(y))


def cpadmm_init(op: PartialCirculant, y: Array) -> CpadmmState:
    batch = y.shape[:-1]
    zeros = jnp.zeros(batch + (op.n,), y.dtype)
    return CpadmmState(x=zeros, v=zeros, z=zeros, mu=zeros, nu=zeros)


def _apply_spec(spec: Array, x: Array, n: int) -> Array:
    return apply_spectrum(spec, x, n)


def cpadmm_tail(
    x: Array, cx: Array, d_diag: Array, pty: Array, mu: Array, nu: Array, p, prox=None
) -> tuple:
    """The iteration tail shared by every CPADMM variant.

    Everything in Alg. 3 after the two circulant applies (x and Cx) is
    the v-update, the z-update, and both dual updates.  Single- and
    multi-device steps call this one definition so the jnp path and the
    fused Pallas kernel (kernels/cpadmm_tail) are pinned against the same
    math.  ``p`` is any params tuple exposing alpha/rho/sigma/tau1/tau2
    (CpadmmParams or DistCpadmmParams).  ``prox=None`` is the paper's
    soft-threshold z-update, under which the whole tail is elementwise
    (the fused-kernel contract); a ``Prox`` swaps the prior.
    Returns (v, z, mu', nu').
    """
    v = d_diag * (pty + p.rho * (cx - mu))
    if prox is None:
        z = soft_threshold(x + nu, p.alpha / p.sigma)
    else:
        z = prox.apply(x + nu, p.alpha / p.sigma)
    mu_new = mu + p.tau1 * (v - cx)
    nu_new = nu + p.tau2 * (x - z)
    return v, z, mu_new, nu_new


def cpadmm_step(
    op: PartialCirculant, const: CpadmmConst, state: CpadmmState, p: CpadmmParams, prox=None
) -> CpadmmState:
    """One Alg. 3 iteration (scaled-dual form).

    x-update:  (rho C^T C + sigma I) x = rho C^T (v + mu) + sigma (z - nu)
               -> two spectra fused: B and C^T (kernel: spectral_pointwise)
    v-update:  (P^T P + rho I) v = P^T y + rho (C x - mu)
    z-update:  soft threshold (Alg. 3 line 5)
    duals:     mu += tau1 (v - Cx);  nu += tau2 (x - z)
    (the last three are :func:`cpadmm_tail` — one fused Pallas pass on the
    kernel backend, kernels/cpadmm_tail)

    The three parts are the named scopes ``cpadmm.xupdate``, ``cpadmm.cx``
    and ``cpadmm.tail`` of the compiled program's ``op_name`` metadata.
    """
    C = op.circ
    n = op.n
    with jax.named_scope("cpadmm.xupdate"):
        rhs = p.rho * C.rmatvec(state.v + state.mu) + p.sigma * (state.z - state.nu)
        x = _apply_spec(const.b_spec, rhs, n)

    with jax.named_scope("cpadmm.cx"):
        cx = C.matvec(x)
    with jax.named_scope("cpadmm.tail"):
        v, z, mu, nu = cpadmm_tail(
            x, cx, const.d_diag, const.Pty, state.mu, state.nu, p, prox=prox
        )
    return CpadmmState(x=x, v=v, z=z, mu=mu, nu=nu)


def default_cpadmm_params(
    alpha: float = 1e-4, rho: float = 0.1, sigma: float = 0.1, tau: float = 1.0
) -> CpadmmParams:
    """Paper Sec. 6 defaults: alpha = 1e-4, sigma = tau = 1e-1."""
    f32 = jnp.float32
    return CpadmmParams(
        alpha=jnp.asarray(alpha, f32),
        rho=jnp.asarray(rho, f32),
        sigma=jnp.asarray(sigma, f32),
        tau1=jnp.asarray(tau, f32),
        tau2=jnp.asarray(tau, f32),
    )

"""The plain reference: CPADMM (arXiv:1707.02244 Alg. 3) in jax.numpy.

It imports nothing of the program and takes only what the benchmark made
from the seed: the circulant's first column(s) and the kept rows.  One
iteration, scaled-dual form, with A = P C, C = F^H diag(c) F:

    x   = (rho C^T C + sigma I)^-1 (rho C^T (v + mu) + sigma (z - nu))
    Cx  = C x
    v   = (P^T P + rho I)^-1 (P^T y + rho (Cx - mu))
    z   = soft(x + nu, alpha / sigma)
    mu += tau (v - Cx);  nu += tau (x - z)

written term by term (six length-n transforms an iteration), float32
throughout.  The answer is z, the sparse iterate.

``lowp=True`` is the control: the same reference with every stored array
rounded to bfloat16 after each operation (the transforms themselves run in
float32 on the rounded values, as jnp.fft has no bfloat16), the step below
the float32 that the configurations state.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.tree_util.register_dataclass, data_fields=["c", "omega"],
                   meta_fields=["n"])
@dataclasses.dataclass(frozen=True)
class Operator:
    """A = P C: the half spectrum of C and the kept rows."""

    c: jax.Array  # (n//2 + 1,) complex64
    omega: jax.Array  # (m,) int32
    n: int


def operator(n: int, cols, omega) -> Operator:
    """The operator whose circulant is the product of the circulants with
    first columns ``cols`` (one for sensing, two for sensing after a blur)."""
    c = jnp.ones((n // 2 + 1,), jnp.complex64)
    for col in cols:
        c = c * jnp.fft.rfft(col, n=n)
    return Operator(c=c, omega=omega, n=n)


def _round(a, lowp: bool):
    if not lowp:
        return a
    if jnp.iscomplexobj(a):
        return jax.lax.complex(_round(a.real, True), _round(a.imag, True))
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _circ(op: Operator, a, spec, lowp: bool):
    q = lambda t: _round(t, lowp)
    return q(jnp.fft.irfft(q(spec * q(jnp.fft.rfft(a, n=op.n))), n=op.n))


def sense(op: Operator, x):
    """y = P C x for each row of x."""
    return jnp.take(_circ(op, x, op.c, False), op.omega, axis=-1)


def _setup(op: Operator, y, rho, sigma, lowp):
    q = lambda t: _round(t, lowp)
    b = q(1.0 / (rho * jnp.abs(op.c) ** 2 + sigma))
    d = jnp.full((op.n,), 1.0 / rho, jnp.float32).at[op.omega].set(1.0 / (1.0 + rho))
    pty = jnp.zeros(y.shape[:-1] + (op.n,), jnp.float32).at[..., op.omega].set(y)
    return b.astype(jnp.complex64), q(d), q(pty)


def _step(op, b, d, pty, s, alpha, rho, sigma, tau, lowp):
    q = lambda t: _round(t, lowp)
    x_, v, z, mu, nu = s
    ct_vmu = _circ(op, q(v + mu), jnp.conj(op.c), lowp)
    rhs = q(q(rho * ct_vmu) + q(sigma * q(z - nu)))
    x = _circ(op, rhs, b, lowp)
    cx = _circ(op, x, op.c, lowp)
    v = q(d * q(pty + q(rho * q(cx - mu))))
    w = q(x + nu)
    z = q(jnp.sign(w) * jnp.maximum(jnp.abs(w) - alpha / sigma, 0.0))
    mu = q(mu + q(tau * q(v - cx)))
    nu = q(nu + q(tau * q(x - z)))
    return (x, v, z, mu, nu)


def cpadmm(op: Operator, y, *, iters: int, alpha: float, rho: float, sigma: float,
           tau: float = 1.0, lowp: bool = False):
    """z after ``iters`` iterations from zeros, for each row of y."""
    b, d, pty = _setup(op, y, rho, sigma, lowp)
    zeros = jnp.zeros(y.shape[:-1] + (op.n,), jnp.float32)
    s = (zeros,) * 5
    s = jax.lax.fori_loop(
        0, iters, lambda _, s: _step(op, b, d, pty, s, alpha, rho, sigma, tau, lowp), s)
    return s[2]


def cpadmm_until(op: Operator, y, tol, min_iters, max_iters, at_iters, *,
                 alpha: float, rho: float, sigma: float, tau: float = 1.0,
                 margin: int = 8, lowp: bool = False):
    """The tolerance rule of a served request, for each row of y.

    A request stops at the first iteration k >= ``min_iters`` whose relative
    change of z, ||z_k - z_{k-1}|| / (||z_{k-1}|| + 1e-12), is <= ``tol``, or
    at ``max_iters``; it has converged if that change is <= ``tol``.  Runs
    every row to ``min(max(max_iters), max(at_iters) + margin)`` and returns
    (z at ``at_iters`` per row, the stopping iteration, converged, z at the
    stopping iteration); a row that has not stopped by then reports one past
    the last iteration run.
    """
    b, d, pty = _setup(op, y, rho, sigma, lowp)
    rows = y.shape[0]
    zeros = jnp.zeros((rows, op.n), jnp.float32)
    last = jnp.minimum(jnp.max(max_iters), jnp.max(at_iters) + margin)
    never = jnp.full((rows,), -1, jnp.int32)

    def body(c):
        k, s, snap, stop, conv, final = c
        new = _step(op, b, d, pty, s, alpha, rho, sigma, tau, lowp)
        k = k + 1
        num = jnp.linalg.norm(new[2] - s[2], axis=-1)
        delta = num / (jnp.linalg.norm(s[2], axis=-1) + 1e-12)
        ends = (stop < 0) & (((k >= min_iters) & (delta <= tol)) | (k >= max_iters))
        conv = jnp.where(ends, delta <= tol, conv)
        stop = jnp.where(ends, k, stop)
        snap = jnp.where((k == at_iters)[:, None], new[2], snap)
        final = jnp.where(ends[:, None], new[2], final)
        return k, new, snap, stop, conv, final

    c = (jnp.int32(0), (zeros,) * 5, zeros, never, jnp.zeros((rows,), bool), zeros)
    k, _, snap, stop, conv, final = jax.lax.while_loop(lambda c: c[0] < last, body, c)
    return snap, jnp.where(stop < 0, k + 1, stop), conv, final


def rel_gap(a, b):
    """Per row ||a - b|| / ||b||."""
    a = a.reshape(a.shape[0], -1).astype(jnp.float32)
    b = b.reshape(b.shape[0], -1).astype(jnp.float32)
    return jnp.linalg.norm(a - b, axis=-1) / jnp.linalg.norm(b, axis=-1)

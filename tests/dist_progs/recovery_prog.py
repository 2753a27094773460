"""Subprocess prog: distributed CPADMM == single-device CPADMM, on 8 devices."""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import RecoveryProblem, solve
from repro.core.circulant import PartialCirculant, gaussian_circulant
from repro.data.synthetic import paper_regime, sparse_signal
from repro.dist.compat import make_mesh
from repro.dist.fft import layout_2d, unlayout_2d
from repro.dist.recovery import make_dist_cpadmm, make_dist_spectrum

mesh = make_mesh((8,), ("model",))
n1, n2 = 32, 32
n = n1 * n2
m, k = paper_regime(n)

# Build the problem in the distributed layout's index space.
x_true = sparse_signal(jax.random.PRNGKey(0), n, k)
C = gaussian_circulant(jax.random.PRNGKey(1), n, normalize=True)
omega = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), n)[:m])
mask = jnp.zeros((n,)).at[omega].set(1.0)
y_full = mask * C.matvec(x_true)  # P^T y in full-length form

ITERS = 400
ALPHA, RHO, SIGMA = 1e-4, 0.01, 0.01

# ---- single-device reference (core solver)
op = PartialCirculant(C, omega.astype(jnp.int32))
prob = RecoveryProblem(op=op, y=jnp.take(C.matvec(x_true), omega), x_true=x_true)
x_ref, tr = solve(prob, "cpadmm", iters=ITERS, record_every=ITERS,
                  alpha=ALPHA, rho=RHO, sigma=SIGMA)
print("single-device final MSE:", float(tr.mse[-1]))

# ---- distributed solver
spec_fn = make_dist_spectrum(mesh)
spec2d = spec_fn(layout_2d(C.col, n1, n2))
solver = make_dist_cpadmm(mesh, n1, n2, ITERS)
z2d = solver(
    spec2d,
    layout_2d(mask, n1, n2),
    layout_2d(y_full, n1, n2),
    jnp.float32(ALPHA),
    jnp.float32(RHO),
    jnp.float32(SIGMA),
)
x_dist = unlayout_2d(z2d)

np.testing.assert_allclose(np.asarray(x_dist), np.asarray(x_ref), atol=2e-4)
mse_dist = float(jnp.mean((x_dist - x_true) ** 2))
print("distributed final MSE:", mse_dist)
assert mse_dist < 1e-4, mse_dist
print("ALL OK")

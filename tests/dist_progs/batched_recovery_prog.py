"""Subprocess prog: batched (data-axis) + rfft distributed CPADMM on 8 fake
devices == 8 sequential single-signal core solves (ISSUE 2 acceptance).

Mesh is (data=2, model=4): B=8 signals ride the data axis two-per-shard
while each signal's four-step rfft stays sharded over 4 model devices —
every transform is still exactly one all-to-all for the whole batch.
"""

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

mesh = make_mesh((2, 4), ("data", "model"))
n1, n2 = 32, 32
n = n1 * n2
B = 8
m, k = paper_regime(n)
ITERS = 400
ALPHA, RHO, SIGMA = 1e-4, 0.01, 0.01

x_true = sparse_signal(jax.random.PRNGKey(0), n, k, batch=(B,))
C = gaussian_circulant(jax.random.PRNGKey(1), n, normalize=True)
omega = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), n)[:m])
mask = jnp.zeros((n,)).at[omega].set(1.0)
y_full = mask * C.matvec(x_true)  # (B, n): P^T y per signal

spec_h = make_dist_spectrum(mesh, rfft=True)(layout_2d(C.col, n1, n2))
solver = make_dist_cpadmm(
    mesh, n1, n2, ITERS, fused=True, rfft=True, batch_axis="data"
)
z2d = solver(
    spec_h,
    layout_2d(mask, n1, n2),
    layout_2d(y_full, n1, n2),
    jnp.float32(ALPHA),
    jnp.float32(RHO),
    jnp.float32(SIGMA),
)
zb = unlayout_2d(z2d)
assert zb.shape == (B, n), zb.shape

# one all-to-all per transform for the WHOLE batch: 2 per fused iteration
hlo = solver.lower(
    spec_h, layout_2d(mask, n1, n2), layout_2d(y_full, n1, n2),
    jnp.float32(ALPHA), jnp.float32(RHO), jnp.float32(SIGMA),
).compile().as_text()
n_a2a = hlo.count("all-to-all")
assert n_a2a >= 2, f"expected all-to-all collectives in the solver, got {n_a2a}"
print(f"collective structure OK ({n_a2a} all-to-all ops for B={B})")

op = PartialCirculant(C, omega.astype(jnp.int32))
worst = 0.0
for b in range(B):
    prob = RecoveryProblem(op=op, y=jnp.take(C.matvec(x_true[b]), omega), x_true=x_true[b])
    x_ref, _ = solve(prob, "cpadmm", iters=ITERS, record_every=ITERS,
                     alpha=ALPHA, rho=RHO, sigma=SIGMA)
    rel = float(jnp.linalg.norm(zb[b] - x_ref) / (jnp.linalg.norm(x_ref) + 1e-30))
    worst = max(worst, rel)
    assert rel <= 1e-5, (b, rel)
print(f"batched B={B} on (2,4) mesh == sequential core solves; worst rel {worst:.2e}")

mse = float(jnp.mean((zb - x_true) ** 2))
assert mse < 1e-4, mse
np.testing.assert_allclose(np.asarray(zb).shape, (B, n))
print("batched final MSE:", mse)
print("ALL OK")

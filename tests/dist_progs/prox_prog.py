"""Subprocess prog: pluggable priors through the plan on 8 real fake devices.

ISSUE 10 acceptance, distributed leg: every prior recovers through the
planned path on an 8-device mesh and matches the single-device solve at
1e-5 rel.  The elementwise priors (l1 / nonneg-l1) ride the one-shard_map
fused CPADMM block (prox=None vs prox=L1Prox() is asserted *bitwise* there,
so the fused lowering demonstrably stayed on); the non-elementwise TV and
wavelet priors take the hybrid core + global-tail lowering, where GSPMD
partitions the prox's rolls over the same mesh.  The TV map-making stack
(shift circulants, (2, 4) data x model mesh) closes with its golden PSNR.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax
import jax.numpy as jnp

from repro.core import RecoveryProblem, partial_gaussian_circulant, solve
from repro.core.mapmaking import (
    build_mapmaking_plan,
    build_mapmaking_problem,
    solve_mapmaking,
)
from repro.data.synthetic import extended_emission, paper_regime, sparse_signal
from repro.dist.compat import make_mesh
from repro.ops import plan
from repro.ops.prox import L1Prox, NonNegL1Prox, TVProx, WaveletProx

N, BATCH, ITERS = 256, 2, 60
KW = dict(alpha=1e-3, rho=0.01, sigma=0.01)

m, k = paper_regime(N)
x_true = sparse_signal(jax.random.PRNGKey(0), N, k, batch=(BATCH,))
op = partial_gaussian_circulant(jax.random.PRNGKey(1), N, m, normalize=True)
prob = RecoveryProblem(op=op, y=op.matvec(x_true), x_true=x_true)
mesh = make_mesh((8,), ("model",))

# every prior: planned 8-device solve == single-device at 1e-5 rel
priors = [
    ("none", None),
    ("l1", L1Prox()),
    ("nonneg-l1", NonNegL1Prox()),
    ("tv", TVProx(shape=(16, 16))),
    ("wavelet", WaveletProx()),
]
for name, prox in priors:
    for method in ("ista", "cpadmm"):
        x_l, _ = solve(prob, method, iters=ITERS, record_every=ITERS,
                       plan=plan(op, prox=prox), **KW)
        x_d, _ = solve(prob, method, iters=ITERS, record_every=ITERS,
                       plan=plan(op, mesh, prox=prox), **KW)
        rel = float(jnp.linalg.norm(x_d - x_l) / (jnp.linalg.norm(x_l) + 1e-30))
        print(f"{name:>9}/{method}: dist vs local rel {rel:.2e}")
        assert rel <= 1e-5, (name, method, rel)

# the fused elementwise block stayed on: None == L1Prox bitwise on the mesh
for method in ("ista", "cpadmm"):
    x0, _ = solve(prob, method, iters=ITERS, record_every=ITERS,
                  plan=plan(op, mesh), **KW)
    x1, _ = solve(prob, method, iters=ITERS, record_every=ITERS,
                  plan=plan(op, mesh, prox=L1Prox()), **KW)
    assert jnp.array_equal(x0, x1), method
print("mesh None == L1Prox bitwise OK")

# rfft layout through the hybrid (non-elementwise) path too
pl_tv_r = plan(op, mesh, prox=TVProx(shape=(16, 16)), rfft=True)
x_r, _ = solve(prob, "cpadmm", iters=ITERS, record_every=ITERS,
               plan=pl_tv_r, **KW)
x_lr, _ = solve(prob, "cpadmm", iters=ITERS, record_every=ITERS,
                plan=plan(op, prox=TVProx(shape=(16, 16))), **KW)
rel = float(jnp.linalg.norm(x_r - x_lr) / (jnp.linalg.norm(x_lr) + 1e-30))
print(f"tv/cpadmm rfft hybrid: dist vs local rel {rel:.2e}")
assert rel <= 1e-5, rel

# the TV map-making acceptance scenario on a (2, 4) data x model mesh
sky = extended_emission(jax.random.PRNGKey(7), 16, 16, n_sources=3)
mp = build_mapmaking_problem(jax.random.PRNGKey(11), sky, [0, 1, 16, 17],
                             blur_order=1.0, subsample=0.5)
mesh2 = make_mesh((2, 4), ("data", "model"))
pl_mm = build_mapmaking_plan(mp, mesh2)
assert "prox=tv" in pl_mm.config.describe()
assert pl_mm.batch_axis == "data"
z_l, m_l = solve_mapmaking(mp, method="cpadmm", iters=600, alpha=1e-4)
z_d, m_d = solve_mapmaking(mp, plan=pl_mm, method="cpadmm", iters=600,
                           alpha=1e-4)
rel = float(jnp.linalg.norm(z_d - z_l) / (jnp.linalg.norm(z_l) + 1e-30))
psnr = float(m_d["psnr_db"])
print(f"mapmaking (2,4) mesh: dist vs local rel {rel:.2e}, map PSNR {psnr:.1f} dB")
assert rel <= 1e-5, rel
assert 44.0 < psnr < 52.0, psnr
print("ALL OK")

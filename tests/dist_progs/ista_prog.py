"""Subprocess prog: distributed CPISTA/FISTA via the plan API on 8 devices.

ISSUE 4 acceptance: the *core* drivers run ista and fista on a real mesh
through ``repro.ops.plan`` — tolerance-stopped (solve_until) and
fixed-budget (solve) — matching the single-device solver to 1e-5 relative
error.  Also checks the collective structure: one planned matvec is exactly
two all-to-alls (forward + inverse four-step transform).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import re

import jax
import jax.numpy as jnp

from repro.core import RecoveryProblem, solve, solve_until
from repro.core.circulant import PartialCirculant, gaussian_circulant
from repro.data.synthetic import paper_regime, sparse_signal
from repro.dist.compat import make_mesh
from repro.ops import plan

mesh = make_mesh((8,), ("model",))
n1, n2 = 32, 32
n = n1 * n2
m, k = paper_regime(n)
ALPHA = 1e-4

x_true = sparse_signal(jax.random.PRNGKey(0), n, k)
C = gaussian_circulant(jax.random.PRNGKey(1), n, normalize=True)
omega = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), n)[:m]).astype(jnp.int32)
op = PartialCirculant(C, omega)
prob = RecoveryProblem(op=op, y=op.matvec(x_true), x_true=x_true)

pl = plan(op, mesh, n1=n1, n2=n2, rfft=True)

# collective structure: one planned matvec = one forward + one inverse
# four-step transform = exactly 2 all-to-alls
hlo = (
    jax.jit(pl.operator.matvec)
    .lower(jnp.zeros((n,), jnp.float32))
    .compile()
    .as_text()
)
# count op *definitions* (operand references are %-prefixed)
n_a2a = len(re.findall(r"(?<!%)\ball-to-all(?:-start)?\(", hlo))
assert n_a2a == 2, f"expected 2 all-to-alls per planned matvec, got {n_a2a}"
print(f"collective structure OK ({n_a2a} all-to-alls per matvec)")

# fixed-budget: ista mid-trajectory, fista at convergence (momentum
# transiently amplifies FFT rounding noise; see tests/test_plan.py)
x_fista = None
for method, iters in (("ista", 300), ("fista", 800)):
    x_ref, _ = solve(prob, method, iters=iters, record_every=iters, alpha=ALPHA)
    x_dist, _ = solve(
        prob, method, iters=iters, record_every=iters, alpha=ALPHA, plan=pl
    )
    rel = float(jnp.linalg.norm(x_dist - x_ref) / (jnp.linalg.norm(x_ref) + 1e-30))
    print(f"{method} solve: rel {rel:.2e}")
    assert rel <= 1e-5, (method, rel)
    if method == "fista":
        x_fista = x_dist

# tolerance-stopped distributed ISTA — the new capability
x_ref, used_ref = solve_until(prob, "ista", tol=1e-7, max_iters=3000, alpha=ALPHA)
x_dist, used = solve_until(
    prob, "ista", tol=1e-7, max_iters=3000, alpha=ALPHA, plan=pl
)
rel = float(jnp.linalg.norm(x_dist - x_ref) / (jnp.linalg.norm(x_ref) + 1e-30))
print(f"ista solve_until: rel {rel:.2e}, iters {int(used)} (core {int(used_ref)})")
assert rel <= 1e-5, rel
assert int(used) > 0

# recovery quality (paper Sec. 6 threshold) on the converged FISTA run —
# plain ISTA's O(1/t) decay needs far more than this budget to get there
mse = float(jnp.mean((x_fista - x_true) ** 2))
print("distributed fista final MSE:", mse)
assert mse < 1e-4, mse
print("ALL OK")

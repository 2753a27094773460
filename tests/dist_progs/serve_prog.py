"""Subprocess prog: the recovery server on an 8-device mesh.

ISSUE 7 acceptance, distributed leg: the continuous-batching dispatcher
runs its bucket engines through ``repro.ops.plan`` on a real mesh — and
bucket isolation holds where it matters most: rfft and full-complex plan
configs lower to *different* collective programs, so requests pinning each
must never share a batch.  Every result (recycled slots included) must
match its solo tolerance-stopped solve to 1e-5 relative.
"""

import dataclasses
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax
import jax.numpy as jnp

from repro.core import RecoveryProblem, solve_until
from repro.core.circulant import PartialCirculant, gaussian_circulant
from repro.data.synthetic import paper_regime
from repro.dist.compat import make_mesh
from repro.ops import PlanConfig
from repro.serve import ManualClock, RecoveryServer, synthetic_workload

mesh = make_mesh((8,), ("model",))
n1, n2 = 32, 32
n = n1 * n2
m, k = paper_regime(n)
RHO = 0.01

C = gaussian_circulant(jax.random.PRNGKey(1), n, normalize=True)
omega = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), n)[:m]).astype(jnp.int32)
op = PartialCirculant(C, omega)

cfg_rfft = PlanConfig(rfft=True, n1=n1, n2=n2)
cfg_full = PlanConfig(rfft=False, n1=n1, n2=n2)

# 6 requests over 2 slots per bucket forces recycling; half pin the rfft
# plan, half the full-complex one — two buckets by construction
base = synthetic_workload(op, 6, rate=1000.0, seed=5, tols=(1e-3, 1e-5),
                          max_iters=400)
reqs = [
    dataclasses.replace(r, plan_config=cfg_rfft if i % 2 else cfg_full)
    for i, r in enumerate(base)
]

srv = RecoveryServer(mesh=mesh, slots=2, round_iters=32, rho=RHO, sigma=RHO,
                     clock=ManualClock())
results = srv.serve(reqs)
stats = srv.stats()
assert len(results) == 6, len(results)
assert stats["buckets"] == 2, stats  # rfft and full-complex never mix
recycled = stats["total"]["recycled"]
assert recycled >= 2, stats  # 6 requests - 2 buckets x 2 cold slots
print(f"2 isolated buckets (rfft / full-complex), {recycled} recycled slots")

by_id = {r.request_id: r for r in reqs}
for res in results:
    req = by_id[res.request_id]
    x_solo, used = solve_until(
        RecoveryProblem(op=op, y=req.y), "cpadmm", tol=req.tol,
        max_iters=req.max_iters, min_iters=req.min_iters, rho=RHO, sigma=RHO,
    )
    rel = float(jnp.linalg.norm(res.x - x_solo)
                / (jnp.linalg.norm(x_solo) + 1e-30))
    print(f"{res.request_id} [{res.bucket.split('|')[-1]}]: "
          f"iters {res.iterations} (solo {int(used)}), rel {rel:.2e}")
    assert rel <= 1e-5, (res.request_id, rel)
    # either converged inside the budget, or exhausted it exactly as the
    # solo run did — never silently stopped early
    assert res.converged or res.iterations == req.max_iters, res.request_id
print("ALL OK")

"""Subprocess prog: plan autotuner on a real 8-device mesh.

ISSUE 6 acceptance: ``plan(op, mesh, tune=True)`` on 8 fake CPU devices
produces a plan whose CPADMM solve matches the untuned default plan —
at 1e-5 relative error when the winner keeps the fp32 wire (re-knobbing
never changes what is computed), or within the plan layer's wire
precision bound when the tuner picks a demoted ``wire_dtype`` (the one
knob that *is* allowed to trade bounded error for wire bytes; a
wire_dtype='fp32' pin restores the exact-parity contract).  Also checks
the two properties that need a non-trivial mesh to mean anything:

  * the cost model's rfft preference corresponds to a real wire-byte win —
    the half-spectrum plan's matvec moves fewer all-to-all bytes than the
    full-complex one at the same n;
  * a warm cache hit skips all scoring/compilation (counter-asserted).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import re
import tempfile

import jax
import jax.numpy as jnp

from repro.core import RecoveryProblem, solve
from repro.core.circulant import PartialCirculant, gaussian_circulant
from repro.data.synthetic import paper_regime, sparse_signal
from repro.dist.compat import make_mesh
from repro.launch import roofline
from repro.ops import plan, tune

# the tuner scores with its devices' peak rates; these CPU devices are
# scored with the v5e entry, named explicitly
roofline.PEAKS[jax.devices()[0].device_kind] = roofline.PEAKS[roofline.V5E]

mesh = make_mesh((8,), ("model",))
n1, n2 = 32, 32
n = n1 * n2
m, k = paper_regime(n)
ALPHA, RHO, SIGMA = 1e-4, 0.01, 0.01

x_true = sparse_signal(jax.random.PRNGKey(0), n, k)
C = gaussian_circulant(jax.random.PRNGKey(1), n, normalize=True)
omega = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), n)[:m]).astype(jnp.int32)
op = PartialCirculant(C, omega)
prob = RecoveryProblem(op=op, y=op.matvec(x_true), x_true=x_true)

cache = tune.PlanCache(os.path.join(tempfile.mkdtemp(), "plan_cache.json"))
tune.reset_counters()

# tune=True (model mode): enumerate + score over the 8-way mesh
tuned_pl = plan(op, mesh, tune=True, tune_opts={"cache": cache})
print("tuned config:", tuned_pl.config.describe())
assert tune.COUNTERS["scored"] > 0 and tune.COUNTERS["cache_misses"] == 1

# tuned solve == untuned solve: exact-parity contract at fp32 wire, the
# documented precision bound when the tuner picked a demoted wire
from repro.ops.plan import WIRE_ERROR_BOUND

default_pl = plan(op, mesh, n1=n1, n2=n2)
kw = dict(iters=300, record_every=300, alpha=ALPHA, rho=RHO, sigma=SIGMA)
x_def, _ = solve(prob, "cpadmm", plan=default_pl, **kw)
x_tun, _ = solve(prob, "cpadmm", plan=tuned_pl, **kw)
rel = float(jnp.linalg.norm(x_tun - x_def) / (jnp.linalg.norm(x_def) + 1e-30))
tol = 1e-5 if tuned_pl.wire_dtype == "fp32" else WIRE_ERROR_BOUND
print(f"tuned vs untuned cpadmm: rel {rel:.2e} (wire={tuned_pl.wire_dtype})")
assert rel <= tol, (rel, tol)

# pinning wire_dtype='fp32' restores the strict re-knob-only contract
pinned_pl = plan(op, mesh, tune=True, wire_dtype="fp32",
                 tune_opts={"cache": cache})
assert pinned_pl.wire_dtype == "fp32"
x_pin, _ = solve(prob, "cpadmm", plan=pinned_pl, **kw)
rel_pin = float(
    jnp.linalg.norm(x_pin - x_def) / (jnp.linalg.norm(x_def) + 1e-30)
)
print(f"fp32-pinned tuned vs untuned cpadmm: rel {rel_pin:.2e}")
assert rel_pin <= 1e-5, rel_pin

# the model's rfft preference is physical: fewer all-to-all bytes on the wire
def _a2a_bytes(p):
    hlo = (
        jax.jit(p.operator.matvec)
        .lower(jnp.zeros((n,), jnp.float32))
        .compile()
        .as_text()
    )
    total = 0
    for line in hlo.splitlines():
        if re.search(r"(?<!%)\ball-to-all(?:-start)?\(", line):
            # LHS is a tuple of per-shard buffers: (c64[4,4]{1,0}, ...)
            lhs = line.split(" all-to-all", 1)[0]
            for dtype_bits, dims in re.findall(r"\b[a-z](\d+)\[([\d,]*)\]", lhs):
                elems = 1
                for d in dims.split(","):
                    elems *= int(d) if d else 1
                total += elems * int(dtype_bits) // 8
    return total


full_b = _a2a_bytes(plan(op, mesh, n1=n1, n2=n2, rfft=False))
half_b = _a2a_bytes(plan(op, mesh, n1=n1, n2=n2, rfft=True))
print(f"all-to-all bytes per matvec: full-complex {full_b}, rfft {half_b}")
assert half_b < full_b, (half_b, full_b)
assert tuned_pl.config.rfft, "model should pick the cheaper-wire rfft plan"

# warm cache: bit-identical config, zero scoring
tune.reset_counters()
warm_pl = plan(op, mesh, tune=True, tune_opts={"cache": cache})
assert warm_pl.config == tuned_pl.config
assert tune.COUNTERS == {
    "scored": 0, "measured": 0, "cache_hits": 1, "cache_misses": 0,
}, tune.COUNTERS
print("warm cache hit: no scoring, no compiles")
print("ALL OK")

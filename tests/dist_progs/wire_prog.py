"""Subprocess prog: wire-compressed collectives on a real 8-device mesh.

ISSUE 8 acceptance, measured on the compiled HLO rather than modeled:

  * the bf16 wire roughly halves the all-to-all payload bytes of one
    distributed rfft matvec vs the fp32 wire (the packed (re, im) planes
    cross the wire as 2-byte elements — asserted at >= 1.8x, < 2.2x);
  * the demoted payload really is 16-bit on the wire: the bf16 program's
    transpose collectives carry u16 buffers (the bitcast that defeats
    XLA:CPU's float-normalization re-promotion), and no f32 all-to-all
    survives;
  * the end-to-end CPADMM solve through the bf16 wire stays within the
    plan layer's documented precision bound of the fp32-wire solve.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import re

import jax
import jax.numpy as jnp

from repro.core import RecoveryProblem, solve
from repro.core.circulant import PartialCirculant, gaussian_circulant
from repro.data.synthetic import paper_regime, sparse_signal
from repro.dist.compat import make_mesh
from repro.ops import plan
from repro.ops.plan import WIRE_ERROR_BOUND

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


def _a2a_buffers(p):
    """(dtype tag, bytes) per all-to-all operand buffer in the compiled
    matvec HLO — same walk as autotune_prog, keeping the dtype visible."""
    hlo = (
        jax.jit(p.operator.matvec)
        .lower(jnp.zeros((n,), jnp.float32))
        .compile()
        .as_text()
    )
    out = []
    for line in hlo.splitlines():
        if re.search(r"(?<!%)\ball-to-all(?:-start)?\(", line):
            lhs = line.split(" all-to-all", 1)[0]
            for dtype, bits, dims in re.findall(
                r"\b([a-z])(\d+)\[([\d,]*)\]", lhs
            ):
                elems = 1
                for d in dims.split(","):
                    elems *= int(d) if d else 1
                out.append((f"{dtype}{bits}", elems * int(bits) // 8))
    return out


pl32 = plan(op, mesh, n1=n1, n2=n2, rfft=True)
pl16 = plan(op, mesh, n1=n1, n2=n2, rfft=True, wire_dtype="bf16")
assert pl16.wire_dtype == "bf16", "guard must accept bf16 on this problem"

buf32 = _a2a_buffers(pl32)
buf16 = _a2a_buffers(pl16)
bytes32 = sum(b for _, b in buf32)
bytes16 = sum(b for _, b in buf16)
ratio = bytes32 / bytes16
print(f"a2a bytes per rfft matvec: fp32 wire {bytes32}, bf16 wire {bytes16} "
      f"({ratio:.2f}x down)")
assert 1.8 <= ratio < 2.2, ratio

# the payload is genuinely 16-bit on the wire — u16 after the bitcast that
# stops XLA:CPU's float-normalization pass from re-promoting the collective
dtypes16 = {d for d, _ in buf16}
assert dtypes16 == {"u16"}, dtypes16
assert all(d in ("c64", "f32") for d, _ in buf32), buf32

# end-to-end: the bf16-wire solve lands within the documented bound
kw = dict(iters=300, record_every=300, alpha=ALPHA, rho=RHO, sigma=SIGMA)
x32, _ = solve(prob, "cpadmm", plan=pl32, **kw)
x16, _ = solve(prob, "cpadmm", plan=pl16, **kw)
rel = float(jnp.linalg.norm(x16 - x32) / (jnp.linalg.norm(x32) + 1e-30))
print(f"bf16-wire vs fp32-wire cpadmm: rel {rel:.2e} "
      f"(bound {WIRE_ERROR_BOUND:.1e})")
assert rel <= WIRE_ERROR_BOUND, rel

# recovery quality is preserved, not just mutual closeness
q32 = float(jnp.linalg.norm(x32 - x_true) / jnp.linalg.norm(x_true))
q16 = float(jnp.linalg.norm(x16 - x_true) / jnp.linalg.norm(x_true))
print(f"recovery error vs truth: fp32 wire {q32:.2e}, bf16 wire {q16:.2e}")
assert q16 <= q32 + WIRE_ERROR_BOUND, (q16, q32)
print("ALL OK")

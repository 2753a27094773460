"""Subprocess prog: hierarchical two-stage transpose on a real 8-device mesh.

ISSUE 9 acceptance, measured on the compiled HLO rather than modeled, on a
``(data=2, host=2, device=2)`` mesh:

  * the hierarchical exchange is *bit-exact* with the flat all-to-all at
    fp32 wires — against both the flat layout on the same factored mesh and
    a plain single-axis mesh — for matvec, rmatvec, every overlap K, and an
    end-to-end CPADMM solve;
  * stage structure in the HLO: each transpose lowers to exactly one
    intra-host all-to-all plus one inter-host collective-permute pair
    (H=2 -> a single rotation hop), i.e. 2 all-to-alls and 2 permutes per
    matvec (fwd + inv transform);
  * the inter-host hop carries exactly ``1/H`` of the flat collective's
    bytes: the sub-block staying on the host is sliced out locally and
    never wired;
  * demoting only the inter-host hop (``inter_wire_dtype='bf16'``) keeps
    the solve within the plan layer's wire bound, and is no worse than
    demoting *both* tiers to bf16 — the intra-host all-to-all still runs
    fp32;
  * the autotuner, given the factored mesh and no hier pin, selects the
    hierarchical exchange on the strength of the two-tier cost model alone.

(The ISSUE's "1e-5 with demoted inter wire" is physically unattainable:
bf16 has 8 mantissa bits, ~2e-3 relative quantization per crossing.  The
pin here is the honest version: fp32 hier is *bit-exact*, and the bf16
inter wire stays within WIRE_ERROR_BOUND of the fp32-wire solve.)
"""

import os
import tempfile

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["REPRO_PLAN_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="hier_prog_cache"), "plan_cache.json"
)

import re

import jax
import jax.numpy as jnp

from repro.core import RecoveryProblem, solve
from repro.core.circulant import PartialCirculant, gaussian_circulant
from repro.data.synthetic import paper_regime, sparse_signal
from repro.dist.compat import make_hier_mesh, make_mesh
from repro.ops import plan
from repro.ops.plan import WIRE_ERROR_BOUND
from repro.ops.tune import tuned_config
from repro.launch import roofline

# the tuner scores with its devices' peak rates; these CPU devices are
# scored with the v5e entry, named explicitly
roofline.PEAKS[jax.devices()[0].device_kind] = roofline.PEAKS[roofline.V5E]

H, D = 2, 2
mesh = make_hier_mesh(2, H, D)  # data=2 x host=2 x device=2
flat_mesh = make_mesh((2, 4), ("data", "model"))
n1, n2 = 32, 32
n = n1 * n2
m, k = paper_regime(n)
ALPHA, RHO, SIGMA = 1e-4, 0.01, 0.01

x_true = sparse_signal(jax.random.PRNGKey(0), n, k)
C = gaussian_circulant(jax.random.PRNGKey(1), n, normalize=True)
omega = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), n)[:m]).astype(jnp.int32)
op = PartialCirculant(C, omega)
prob = RecoveryProblem(op=op, y=op.matvec(x_true), x_true=x_true)


def _collective_lines(p, kind):
    """One ``(dtypes, total result bytes)`` entry per ``kind`` collective op
    in the compiled matvec HLO — the wire_prog buffer walk, aggregated per
    op because XLA may emit the tuple form (one result shape per split) for
    multi-axis collectives."""
    hlo = (
        jax.jit(p.operator.matvec)
        .lower(jnp.zeros((n,), jnp.float32))
        .compile()
        .as_text()
    )
    out = []
    for line in hlo.splitlines():
        if re.search(rf"(?<!%)\b{kind}(?:-start)?\(", line):
            lhs = line.split(f" {kind}", 1)[0]
            bufs = []
            for dtype, bits, dims in re.findall(
                r"\b([a-z])(\d+)\[([\d,]*)\]", lhs
            ):
                elems = 1
                for d in dims.split(","):
                    elems *= int(d) if d else 1
                bufs.append((f"{dtype}{bits}", elems * int(bits) // 8))
            if bufs:
                out.append((frozenset(d for d, _ in bufs),
                            sum(b for _, b in bufs)))
    return out


x = jax.random.normal(jax.random.PRNGKey(3), (n,), jnp.float32)
yfull = jnp.zeros((n,)).at[omega].set(op.matvec(x_true))

pl_single = plan(op, flat_mesh, n1=n1, n2=n2, rfft=True)
pl_flat = plan(op, mesh, n1=n1, n2=n2, rfft=True, axis_name=("host", "device"))
pl_hier = plan(op, mesh, n1=n1, n2=n2, rfft=True, hier_axes=(H, D))

ref = pl_single.matvec(x)
assert jnp.array_equal(pl_flat.matvec(x), ref), "flat-on-factored-mesh drifted"
assert jnp.array_equal(pl_hier.matvec(x), ref), "hier matvec not bit-exact"
assert jnp.array_equal(pl_hier.rmatvec(yfull), pl_single.rmatvec(yfull))
for K in (2, 4):
    pK = plan(op, mesh, n1=n1, n2=n2, rfft=True, hier_axes=(H, D), overlap=K)
    assert jnp.array_equal(pK.matvec(x), ref), f"hier overlap={K} drifted"
print("fp32 hier: bit-exact vs flat (both meshes), all overlap K")

# -- HLO stage structure + the 1/H inter-host byte pin ----------------------
a2a_flat = _collective_lines(pl_flat, "all-to-all")
a2a_hier = _collective_lines(pl_hier, "all-to-all")
cp_hier = _collective_lines(pl_hier, "collective-permute")
assert not _collective_lines(pl_flat, "collective-permute")
# one matvec = fwd + inv transform: 2 intra-host all-to-alls and, at H=2,
# one rotation permute each -> 2 collective-permutes
assert len(a2a_flat) == 2, a2a_flat
assert len(a2a_hier) == 2, a2a_hier
assert len(cp_hier) == 2, cp_hier
flat_bytes = sum(b for _, b in a2a_flat)
intra_bytes = sum(b for _, b in a2a_hier)
inter_bytes = sum(b for _, b in cp_hier)
print(f"per-matvec wire bytes: flat a2a {flat_bytes}, hier intra {intra_bytes} "
      f"+ inter {inter_bytes}")
# the intra stage reshuffles the full payload on the fast tier...
assert intra_bytes == flat_bytes, (intra_bytes, flat_bytes)
# ...and the inter-host hop carries exactly 1/H of the flat bytes
assert inter_bytes * H == flat_bytes, (inter_bytes, H, flat_bytes)

# -- per-tier wire precision -------------------------------------------------
kw = dict(iters=300, record_every=300, alpha=ALPHA, rho=RHO, sigma=SIGMA)
x32, _ = solve(prob, "cpadmm", plan=pl_hier, **kw)
assert jnp.array_equal(
    x32, solve(prob, "cpadmm", plan=pl_flat, **kw)[0]
), "hier cpadmm not bit-exact"

pl_inter16 = plan(op, mesh, n1=n1, n2=n2, rfft=True, hier_axes=(H, D),
                  inter_wire_dtype="bf16")
assert pl_inter16.inter_wire_dtype == "bf16", "guard must accept bf16 inter"
# the demoted hop really is 16-bit on the wire; the intra tier stays f32
assert {d for ds, _ in _collective_lines(pl_inter16, "collective-permute")
        for d in ds} == {"u16"}
assert all(
    d in ("c64", "f32")
    for ds, _ in _collective_lines(pl_inter16, "all-to-all") for d in ds
)
x16, _ = solve(prob, "cpadmm", plan=pl_inter16, **kw)
rel16 = float(jnp.linalg.norm(x16 - x32) / (jnp.linalg.norm(x32) + 1e-30))
print(f"bf16-inter vs fp32 cpadmm: rel {rel16:.2e} (bound {WIRE_ERROR_BOUND:.1e})")
assert rel16 <= WIRE_ERROR_BOUND, rel16

# demoting only 1/H of the bytes must not be worse than demoting all of them
pl_both16 = plan(op, mesh, n1=n1, n2=n2, rfft=True, hier_axes=(H, D),
                 wire_dtype="bf16", inter_wire_dtype="bf16")
xb, _ = solve(prob, "cpadmm", plan=pl_both16, **kw)
relb = float(jnp.linalg.norm(xb - x32) / (jnp.linalg.norm(x32) + 1e-30))
print(f"bf16-both vs fp32 cpadmm: rel {relb:.2e}")
assert rel16 <= relb * 1.5 + 1e-12, (rel16, relb)

# -- the tuner picks hier unaided on the factored mesh -----------------------
cfg = tuned_config(op, mesh, batch=2, pins={"n1": n1, "n2": n2, "rfft": True,
                                            "fused": True})
print(f"tuned: {cfg.describe()}")
assert cfg.hier_axes == (H, D), cfg
print("ALL OK")

"""Subprocess prog: compressed-domain deblurring through the plan on 8 devices.

ISSUE 5 acceptance: the paper's flagship Sec. 7 scenario — the joint
sensing+blur operator A = P (C B) — runs distributed on a real (2, 4)
data x model mesh via ``build_deblur_plan``: a 4-frame stack shards over
the data axis, each frame's four-step transforms over the model axis, and
the composed spectrum spec(C)·spec(B) is laid out and sharded once (no
time-domain round trip).  Pins: the planned solve matches the single-device
one at 1e-5 rel per frame, every frame clears the 45 dB multiframe golden
PSNR pin, a planned matvec is exactly 2 all-to-alls, and the direct
spectrum layout agrees with the four-step transform of the first column on
all 8 devices.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import re

import jax
import jax.numpy as jnp

from repro.core import RecoveryProblem, solve
from repro.core.deblur import (
    build_deblur_plan,
    build_multiframe_deblur_problem,
    deblur_metrics,
)
from repro.data.synthetic import starfield
from repro.dist.compat import make_mesh
from repro.dist.fft import layout_2d
from repro.dist.recovery import make_dist_spectrum

F, H, W = 4, 32, 32
ITERS = 800
KW = dict(alpha=1e-3, rho=0.01, sigma=0.01)

imgs = jnp.stack(
    [starfield(jax.random.PRNGKey(i), h=H, w=W, density=0.05, n_blobs=2)
     for i in range(F)]
)
p = build_multiframe_deblur_problem(
    jax.random.PRNGKey(1), imgs, blur_order=5, subsample=0.5, sensing="romberg"
)
prob = RecoveryProblem(op=p.op, y=p.y, x_true=imgs.reshape(F, -1))

mesh = make_mesh((2, 4), ("data", "model"))
pl = build_deblur_plan(p, mesh, rfft=True)
assert (pl.n1, pl.n2) == (H, W), (pl.n1, pl.n2)
assert pl.batch_axis == "data", pl.batch_axis

# the direct spectrum re-layout must equal the four-step transform of the
# first column on the real 8-device mesh (half layout, padded columns)
spec_fft = make_dist_spectrum(mesh, axis_name="model", rfft=True)(
    layout_2d(p.op.circ.col, pl.n1, pl.n2)
)
scale = float(jnp.max(jnp.abs(spec_fft)))
err = float(jnp.max(jnp.abs(pl.spec2d - spec_fft))) / scale
print(f"composed-spectrum layout vs four-step FFT: max rel {err:.2e}")
assert err <= 1e-5, err

# collective structure: one planned joint matvec = fwd + inv transform =
# exactly 2 all-to-alls (op *definitions*; operand references are %-prefixed)
hlo = (
    jax.jit(pl.operator.matvec)
    .lower(jnp.zeros((H * W,), jnp.float32))
    .compile()
    .as_text()
)
n_a2a = len(re.findall(r"(?<!%)\ball-to-all(?:-start)?\(", hlo))
assert n_a2a == 2, f"expected 2 all-to-alls per planned deblur matvec, got {n_a2a}"
print(f"collective structure OK ({n_a2a} all-to-alls per matvec)")

# single-device reference vs the planned distributed solve, per frame
x_ref, _ = solve(prob, "cpadmm", iters=ITERS, record_every=ITERS, **KW)
x_dist, _ = solve(prob, "cpadmm", iters=ITERS, record_every=ITERS, plan=pl, **KW)
for f in range(F):
    rel = float(
        jnp.linalg.norm(x_dist[f] - x_ref[f])
        / (jnp.linalg.norm(x_ref[f]) + 1e-30)
    )
    print(f"frame {f}: planned vs single-device rel {rel:.2e}")
    assert rel <= 1e-5, (f, rel)

# the multiframe golden PSNR pin through the planned path
psnr = deblur_metrics(p, x_dist)["psnr_db"]
print("per-frame PSNR (dB):", [f"{float(v):.2f}" for v in psnr])
assert (psnr >= 45.0).all(), psnr

# full-complex path (rfft=False) stays pinned too, shorter budget
pl_full = build_deblur_plan(p, mesh, rfft=False)
x_ref300, _ = solve(prob, "cpadmm", iters=300, record_every=300, **KW)
x_full, _ = solve(prob, "cpadmm", iters=300, record_every=300, plan=pl_full, **KW)
rel = float(jnp.linalg.norm(x_full - x_ref300) / jnp.linalg.norm(x_ref300))
print(f"full-complex planned vs single-device rel {rel:.2e}")
assert rel <= 1e-5, rel
print("ALL OK")

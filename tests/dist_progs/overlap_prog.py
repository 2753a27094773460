"""Subprocess prog: overlapped chunked-transpose FFT pipeline on 8 fake
devices — overlap=K must match the monolithic overlap=1 path at 1e-5 rel
with real (non-trivial) all-to-alls, and the chunking must actually multiply
the collective count in the lowered HLO (K chunk-collectives in flight is
the latency-hiding structure XLA schedules around).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.circulant import gaussian_circulant
from repro.dist.compat import make_mesh
from repro.dist.fft import (
    layout_2d,
    make_distributed_fft,
    make_distributed_matvec,
    make_distributed_rfft,
)
from repro.dist.recovery import make_dist_cpadmm, make_dist_spectrum

mesh = make_mesh((8,), ("model",))
n1, n2 = 64, 32
n = n1 * n2


def rel(got, want):
    return float(jnp.linalg.norm(got - want) / (jnp.linalg.norm(want) + 1e-30))


x2d = layout_2d(jax.random.normal(jax.random.PRNGKey(0), (n,)), n1, n2)

# fft / rfft: overlap=K == overlap=1, and roundtrips close
for K in (2, 4):
    f1, i1 = make_distributed_fft(mesh, n1, n2, overlap=1)
    fk, ik = make_distributed_fft(mesh, n1, n2, overlap=K)
    F1, Fk = f1(x2d.astype(jnp.complex64)), fk(x2d.astype(jnp.complex64))
    assert rel(Fk, F1) <= 1e-5, (K, rel(Fk, F1))
    assert rel(jnp.real(ik(Fk)), x2d) <= 1e-4

    r1, ir1 = make_distributed_rfft(mesh, n1, n2, overlap=1)
    rk, irk = make_distributed_rfft(mesh, n1, n2, overlap=K)
    H1, Hk = r1(x2d), rk(x2d)
    assert rel(Hk, H1) <= 1e-5, (K, rel(Hk, H1))
    assert rel(irk(Hk), x2d) <= 1e-5
    print(f"fft/rfft overlap={K} OK")

# chunked collective structure: the forward transform must lower to K
# all-to-alls (one per chunk) instead of 1 — independent ops XLA's async
# scheduler can put in flight while the next chunk's FFT runs
for K in (1, 4):
    fk, _ = make_distributed_fft(mesh, n1, n2, overlap=K)
    hlo = fk.lower(x2d.astype(jnp.complex64)).compile().as_text()
    count = hlo.count("all-to-all-start(") + hlo.count(" all-to-all(")
    assert count >= K, f"overlap={K}: expected >= {K} all-to-alls, got {count}"
    print(f"collective structure overlap={K} OK ({count} all-to-all ops)")

# distributed matvec with overlap == monolithic matvec, both layouts
C = gaussian_circulant(jax.random.PRNGKey(1), n, normalize=True)
spec_h = make_distributed_rfft(mesh, n1, n2)[0](layout_2d(C.col, n1, n2))
mv1 = make_distributed_matvec(mesh, rfft=True, overlap=1)
mv4 = make_distributed_matvec(mesh, rfft=True, overlap=4)
for transpose in (False, True):
    assert rel(mv4(spec_h, x2d, transpose), mv1(spec_h, x2d, transpose)) <= 1e-5
print("overlapped matvec OK")

# end-to-end: overlapped fused rfft solver == monolithic solver on 8 devices
mask = jnp.zeros((n,)).at[jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), n)[: n // 2])].set(1.0)
y_full = mask * C.matvec(jax.random.normal(jax.random.PRNGKey(3), (n,)))
spec = make_dist_spectrum(mesh, rfft=True)(layout_2d(C.col, n1, n2))
args = (
    spec,
    layout_2d(mask, n1, n2),
    layout_2d(y_full, n1, n2),
    jnp.float32(1e-4),
    jnp.float32(0.01),
    jnp.float32(0.01),
)
z1 = make_dist_cpadmm(mesh, n1, n2, 100, fused=True, rfft=True, overlap=1)(*args)
z4 = make_dist_cpadmm(mesh, n1, n2, 100, fused=True, rfft=True, overlap=4)(*args)
r = rel(z4, z1)
assert r <= 1e-5, r
print(f"overlapped solver == monolithic solver on 8 devices (rel {r:.2e})")

np.testing.assert_allclose(np.asarray(z4).shape, np.asarray(z1).shape)
print("ALL OK")

"""Subprocess prog: distributed four-step FFT correctness on 8 fake devices."""

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
    freq_flat,
    layout_2d,
    make_distributed_fft,
    make_distributed_matvec,
    unlayout_2d,
)

mesh = make_mesh((8,), ("model",))
n1, n2 = 64, 32
n = n1 * n2

key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (n,))
a2d = layout_2d(x, n1, n2)

fft2d, ifft2d = make_distributed_fft(mesh, n1, n2)
F = fft2d(a2d.astype(jnp.complex64))

# forward: F.reshape(-1) must equal fft(x)
want = jnp.fft.fft(x.astype(jnp.complex64))
np.testing.assert_allclose(np.asarray(freq_flat(F)), np.asarray(want), rtol=2e-3, atol=2e-2)
print("fft fwd OK")

# roundtrip
back = ifft2d(F)
np.testing.assert_allclose(np.asarray(jnp.real(back)), np.asarray(a2d), atol=1e-4)
print("fft roundtrip OK")

# distributed circulant matvec == single-device oracle
C = gaussian_circulant(jax.random.PRNGKey(1), n, normalize=True)
spec2d = fft2d(layout_2d(C.col, n1, n2).astype(jnp.complex64))
mv = make_distributed_matvec(mesh)
got = unlayout_2d(mv(spec2d, a2d))
want_mv = C.matvec(x)
np.testing.assert_allclose(np.asarray(got), np.asarray(want_mv), atol=5e-4)
print("matvec OK")

got_t = unlayout_2d(mv(spec2d, a2d, True))
want_t = C.rmatvec(x)
np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t), atol=5e-4)
print("matvec_T OK")

# communication structure: exactly 2 all-to-alls per distributed matvec
hlo = mv.lower(spec2d, a2d).compile().as_text()
n_a2a = hlo.count("all-to-all")
assert n_a2a >= 2, f"expected all-to-all collectives, found {n_a2a}"
print(f"collective structure OK ({n_a2a} all-to-all ops)")
print("ALL OK")

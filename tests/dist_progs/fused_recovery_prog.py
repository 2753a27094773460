import os
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
import jax
import jax.numpy as jnp
import numpy as np
from repro.core.circulant import gaussian_circulant
from repro.data.synthetic import paper_regime, sparse_signal
from repro.dist.compat import make_mesh
from repro.dist.fft import layout_2d, unlayout_2d
from repro.dist.recovery import make_dist_cpadmm, make_dist_spectrum

mesh = make_mesh((8,), ("model",))
n1, n2 = 32, 32
n = n1*n2
m, k = paper_regime(n)
x_true = sparse_signal(jax.random.PRNGKey(0), n, k)
C = gaussian_circulant(jax.random.PRNGKey(1), n, normalize=True)
omega = jnp.sort(jax.random.permutation(jax.random.PRNGKey(2), n)[:m])
mask = jnp.zeros((n,)).at[omega].set(1.0)
y_full = mask * C.matvec(x_true)
spec2d = make_dist_spectrum(mesh)(layout_2d(C.col, n1, n2))
a = (spec2d, layout_2d(mask, n1, n2), layout_2d(y_full, n1, n2),
     jnp.float32(1e-4), jnp.float32(0.01), jnp.float32(0.01))
zb = unlayout_2d(make_dist_cpadmm(mesh, n1, n2, 400)(*a))
zf = unlayout_2d(make_dist_cpadmm(mesh, n1, n2, 400, fused=True)(*a))
np.testing.assert_allclose(np.asarray(zf), np.asarray(zb), atol=3e-5)
print("fused == baseline, mse:", float(jnp.mean((zf-x_true)**2)))
print("ALL OK")

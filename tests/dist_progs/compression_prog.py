"""Subprocess prog: CS gradient compression as a cross-replica collective.

Checks (8 fake devices, 'data' axis):
  1. compressed_mean reduces a *sparse* per-replica gradient family with low
     error vs exact pmean,
  2. wire bytes are n/ratio of the dense all-reduce,
  3. error feedback drives the residual accumulation: over steps, the mean
     decoded gradient tracks the true mean (compression error does not
     accumulate as a bias).
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.compression import (
    compressed_mean,
    compression_wire_bytes,
    identity_wire_bytes,
    make_compressor,
)
from repro.dist.compat import make_mesh, shard_map

mesh = make_mesh((8,), ("data",))
DIM = 4096
RATIO = 8
spec, state0 = make_compressor(jax.random.PRNGKey(7), DIM, ratio=RATIO, decode_iters=50, alpha=3e-3)

print("wire bytes:", compression_wire_bytes(spec), "vs dense", identity_wire_bytes(DIM))
assert compression_wire_bytes(spec) * (RATIO - 1) < identity_wire_bytes(DIM)

# sparse per-replica gradients: shared support (top-k structure), distinct
# values.  k chosen within the CS budget: m = DIM/ratio = 512 measurements
# recover k=64 reliably (m ~ 8k > 2k log(n/k)); denser gradients rely on the
# error-feedback path (checked below).
k = DIM // 64
support = jax.random.permutation(jax.random.PRNGKey(0), DIM)[:k]
vals = jax.random.normal(jax.random.PRNGKey(1), (8, k))
g_all = jnp.zeros((8, DIM)).at[:, support].set(vals)
g_mean_true = jnp.mean(g_all, axis=0)


def worker(g, st):
    out, new_st = compressed_mean(spec, st, g, "data")
    return out, new_st


fn = shard_map(
    worker,
    mesh=mesh,
    in_specs=(P("data", None), P(None)),
    out_specs=(P("data", None), P(None)),
    check_vma=False,
)

state = state0
outs, state = jax.jit(fn)(g_all, state)
err = float(jnp.linalg.norm(outs[0] - g_mean_true) / jnp.linalg.norm(g_mean_true))
print("one-shot relative decode error:", err)
assert err < 0.35, err

# error feedback over repeated steps with the SAME gradient: time-averaged
# decoded gradient must converge to the truth (EF-SGD guarantee shape)
accum = jnp.zeros((DIM,))
state = state0
STEPS = 30
for _ in range(STEPS):
    outs, state = jax.jit(fn)(g_all, state)
    accum = accum + outs[0]
avg = accum / STEPS
err_avg = float(jnp.linalg.norm(avg - g_mean_true) / jnp.linalg.norm(g_mean_true))
print("time-averaged relative error with EF:", err_avg)
assert err_avg < err * 0.7, (err_avg, err)
print("ALL OK")

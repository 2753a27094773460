"""Subprocess prog: sharded train step on a (2,4) mesh matches the math and
runs collectives; checkpoint save -> elastic restore onto a different mesh."""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import tempfile

import jax
import numpy as np

from repro.ckpt import checkpoint as ckpt
from repro.configs.registry import smoke_config
from repro.dist.compat import make_mesh
from repro.dist.sharding import activate_rules, rules_for_arch
from repro.launch.partition import batch_shardings, train_state_shardings
from repro.models import steps
from repro.optim.adamw import AdamWConfig

cfg = smoke_config("codeqwen15_7b")
opt_cfg = AdamWConfig(lr_peak=1e-3, warmup_steps=2, total_steps=10)

mesh = make_mesh((2, 4), ("data", "model"))
rules = rules_for_arch(cfg, mesh)

B, S = 8, 32
batch = {
    "tokens": jax.random.randint(jax.random.PRNGKey(0), (B, S + 1), 0, cfg.vocab)
}

# ---- single-device reference
state0 = steps.init_train_state(jax.random.PRNGKey(42), cfg, opt_cfg)
ref_step = jax.jit(steps.make_train_step(cfg, opt_cfg))
_, ref_metrics = ref_step(state0, batch)
ref_loss = float(ref_metrics["loss"])
print("single-device loss:", ref_loss)

# ---- sharded
state_shape = jax.eval_shape(
    lambda: steps.init_train_state(jax.random.PRNGKey(42), cfg, opt_cfg)
)
state_sh = train_state_shardings(mesh, state_shape, rules)
batch_sh = batch_shardings(mesh, jax.eval_shape(lambda: batch), rules)

state_dist = jax.tree.map(
    lambda a, s: jax.device_put(np.asarray(a), s), state0, state_sh
)
batch_dist = jax.tree.map(
    lambda a, s: jax.device_put(np.asarray(a), s), batch, batch_sh
)

with activate_rules(rules, mesh):
    train_step = jax.jit(
        steps.make_train_step(cfg, opt_cfg),
        in_shardings=(state_sh, batch_sh),
        out_shardings=None,
    )
    new_state, metrics = train_step(state_dist, batch_dist)
    dist_loss = float(metrics["loss"])
print("sharded loss:", dist_loss)
assert abs(dist_loss - ref_loss) / ref_loss < 2e-2, (dist_loss, ref_loss)

# params actually sharded?
wq = new_state.params["segments"][0]["attn"]["wq"]
n_shards = len({d for s in wq.addressable_shards for d in [s.device]})
assert n_shards == 8, n_shards
print("param sharding OK")

# ---- checkpoint on (2,4), elastic restore onto (4,2)
tmp = tempfile.mkdtemp()
ckpt.save(tmp, 1, jax.device_get(new_state))
mesh2 = make_mesh((4, 2), ("data", "model"))
rules2 = rules_for_arch(cfg, mesh2)
state_sh2 = train_state_shardings(mesh2, state_shape, rules2)
step_no, restored = ckpt.restore(tmp, None, state_shape, state_sh2)
assert step_no == 1
np.testing.assert_allclose(
    np.asarray(jax.device_get(restored.params["final_norm"]["scale"])),
    np.asarray(jax.device_get(new_state.params["final_norm"]["scale"])),
)
# one more step on the NEW mesh from the restored state
batch_sh2 = batch_shardings(mesh2, jax.eval_shape(lambda: batch), rules2)
batch2 = jax.tree.map(lambda a, s: jax.device_put(np.asarray(a), s), batch, batch_sh2)
with activate_rules(rules2, mesh2):
    train_step2 = jax.jit(
        steps.make_train_step(cfg, opt_cfg), in_shardings=(state_sh2, batch_sh2)
    )
    _, m2 = train_step2(restored, batch2)
print("post-restore loss:", float(m2["loss"]))
assert np.isfinite(float(m2["loss"]))
print("elastic restore OK")
print("ALL OK")

#!/usr/bin/env python3
"""Compile each served cell's round program at cell size for a described
TPU v5e, without a chip, and print its ``memory_analysis`` (arguments,
outputs and the temporaries that ``peak_bytes_in_use`` of a run does not
show).

    JAX_PLATFORMS=cpu python3 bench/compile_cells.py [cell ...]

What compiles is a copy of one round of the serving engine: the same
``make_stepper`` / ``until_step`` loop over ``slots`` lanes that
``repro.serve.engine`` jits, rebuilt here from those public functions,
because the engine builds its arrays, and runs its first step, on the
default device, which is the CPU here.  Its numbers describe that copy;
they drift from the engine's own program if the engine's round changes.
The Pallas kernels are compiled for the chip, not interpreted: this
script, and not the program, tells them the backend is a TPU.
"""

from __future__ import annotations

import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402


def _shapes(tree, sharding):
    import jax

    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
                        tree)


def serve_round(cfg, dep, tr, sharding):
    import jax
    import jax.numpy as jnp

    from repro.core.solvers import (
        RecoveryProblem,
        make_stepper,
        until_active,
        until_init,
        until_step,
    )
    from repro.ops import plan

    slots, rounds = tr["slots"], tr["round_iters"]
    raw, y = jax.eval_shape(lambda k: dep.build(cfg, k, slots), jax.random.PRNGKey(0))
    op = jax.eval_shape(functools.partial(dep.program_operator, cfg), raw)

    def round_fn(op, y, tol, mn, mx):
        stepper = make_stepper(RecoveryProblem(op=op, y=y), cfg["method"],
                               alpha=cfg["alpha"], rho=cfg["rho"], sigma=cfg["sigma"],
                               plan=plan(op, config=dep.plan_config(cfg)))
        u, batch = until_init(stepper)

        def cond(c):
            return jnp.logical_and(c[1] < rounds, jnp.any(until_active(c[0], tol, mn, mx)))

        def body(c):
            return until_step(stepper, c[0], tol, mn, mx, batch), c[1] + 1

        u, _ = jax.lax.while_loop(cond, body, (u, jnp.int32(0)))
        return u, stepper.extract(u.state)

    vec = lambda dt: jax.ShapeDtypeStruct((slots,), dt, sharding=sharding)
    return jax.jit(round_fn).lower(_shapes(op, sharding), _shapes(y, sharding),
                                   vec(jnp.float32), vec(jnp.int32), vec(jnp.int32))


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels.cpadmm_tail.ops as tail_ops

    tail_ops.interpret_default = lambda: False  # compile the kernels for the chip
    jax.config.update("jax_enable_compilation_cache", False)
    benchmark = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    names = (argv if argv else None) or [c["name"] for c in benchmark["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in names:
        cell = harness.find_cell(benchmark, name)
        cfg = harness.load_json(os.path.join(BENCH, "configs", cell["config"] + ".json"))
        dep = harness.load_module(os.path.join(BENCH, "configs", cell["config"] + ".py"),
                                  "cfg_" + cell["config"])
        tr = harness.load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
        if tr["driver"] != "serve":
            raise SystemExit(f"{name}: only served cells are compiled here")
        compiled = serve_round(cfg, dep, tr, one_chip).compile()
        m = compiled.memory_analysis()
        print(json.dumps({
            "cell": name, "device": "v5e (described, compile only)",
            "program": "copy of the serving engine's round",
            "kernels": compiled.as_text().count("tpu_custom_call"),
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "generated_code_bytes": m.generated_code_size_in_bytes}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

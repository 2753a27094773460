"""The yardstick's parts: the configuration at a tiny size, the seed, the
schedule of served traffic and the reference's tolerance rule."""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

import harness
import reference


def _config(name, **over):
    cfg = harness.load_json(os.path.join(harness.BENCH, "configs", name + ".json"))
    dep = harness.load_module(os.path.join(harness.BENCH, "configs", name + ".py"),
                              "t_" + name)
    return {**cfg, **over}, dep


TINY = {"height": 16, "width": 32, "n": 512, "m": 256}


def test_configuration_builds_and_its_operators_agree():
    cfg, dep = _config("deblur_sec7_1024", **TINY)
    raw, y = jax.jit(lambda k: dep.build(cfg, k, 3))(jax.random.PRNGKey(7))
    assert y.shape == (3, cfg["m"]) and bool(jnp.isfinite(y).all())
    assert sorted(np.asarray(raw["omega"]).tolist()) == sorted(set(np.asarray(raw["omega"]).tolist()))
    op = dep.program_operator(cfg, raw)
    ref = dep.reference_operator(cfg, raw)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, cfg["n"]))
    np.testing.assert_allclose(op.matvec(x), reference.sense(ref, x), rtol=1e-4, atol=1e-5)


def test_same_seed_same_inputs():
    cfg, dep = _config("deblur_sec7_1024", **TINY)
    import gen

    big = 2 ** 40 + 12345
    a = jax.jit(lambda k: dep.build(cfg, k, 2))(gen.key_from_seed(big))
    b = jax.jit(lambda k: dep.build(cfg, k, 2))(gen.key_from_seed(big))
    c = jax.jit(lambda k: dep.build(cfg, k, 2))(gen.key_from_seed(big + 1))
    assert bool((a[1] == b[1]).all()) and not bool((a[1] == c[1]).all())


def test_served_schedule_same_set_other_order():
    serve = harness.load_module(os.path.join(harness.BENCH, "drivers", "serve.py"), "t_serve")
    mix = [{"name": "a", "tol": 1e-3, "share": 3}, {"name": "b", "tol": 1e-5, "share": 1}]
    t1, tol1 = serve.schedule(11, 1.3, 51.0, mix)
    t2, tol2 = serve.schedule(2 ** 33 + 5, 1.3, 51.0, mix)
    assert len(t1) == len(t2) == round(1.3 * 51.0)
    assert t1[0] == 0.0 and t1[-1] < 51.0 and np.all(np.diff(t1) > 0)
    gaps = lambda t: np.sort(np.diff(np.append(t, 51.0)))  # the last gap runs to the end
    np.testing.assert_allclose(gaps(t1), gaps(t2), rtol=1e-9)
    assert sorted(tol1) == sorted(tol2) and (tol1 == 1e-5).sum() == len(t1) // 4
    assert not np.array_equal(t1, t2)


def test_reference_until_matches_fixed_iterations():
    cfg, dep = _config("deblur_sec7_1024", **TINY)
    raw, y = jax.jit(lambda k: dep.build(cfg, k, 2))(jax.random.PRNGKey(3))
    ref = dep.reference_operator(cfg, raw)
    kw = dict(alpha=cfg["alpha"], rho=cfg["rho"], sigma=cfg["sigma"])
    at = jnp.asarray([30, 45], jnp.int32)
    snap, stop, conv, final = jax.jit(functools.partial(reference.cpadmm_until, **kw))(
        ref, y, jnp.asarray([1e-1, 3e-2]), 10, 60, at)
    assert np.all(np.asarray(stop) <= 53)  # both stopped inside the run
    for i in range(2):
        z = reference.cpadmm(ref, y[i:i + 1], iters=int(at[i]), **kw)
        # equal to rounding (batched and single transforms differ in order)
        assert float(reference.rel_gap(snap[i:i + 1], z)[0]) < 1e-5
        zs = reference.cpadmm(ref, y[i:i + 1], iters=int(stop[i]), **kw)
        assert float(reference.rel_gap(final[i:i + 1], zs)[0]) < 1e-5
        zn = reference.cpadmm(ref, y[i:i + 1], iters=int(at[i]) + 1, **kw)
        assert float(reference.rel_gap(snap[i:i + 1], zn)[0]) > 1e-4

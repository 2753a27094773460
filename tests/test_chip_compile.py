"""The main-path Pallas kernels compile for a TPU v5e (no chip needed).

Interpret mode on the CPU cannot see what the chip's compiler refuses: the
Mosaic tiling rules ((8, 128) blocks, XLA's 1024-element tiles for long
1-D arrays).  Each test here lowers one kernel at a width the recovery
paths run — the n = 2^24, B = 4 paper-regime block, the n = 2^20 serving
batch, four-step wire chunks — for one chip of a described ``v5e:2x2``
topology, and asserts the compiled program holds the kernel
(``tpu_custom_call``).  The inverse real FFT is compiled the same way, to
assert that its program holds no lane-axis ``reverse``.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU compiler
library, and every test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cpadmm_tail.ops import fused_cpadmm_tail
from repro.kernels.soft_threshold.ops import fused_admm_update, fused_ista_update
from repro.kernels.spectral_pointwise.ops import spectral_update
from repro.kernels.wire_pack.ops import pack_wire, unpack_wire
from repro.ops.spectral import irfft

N_PAPER = 1 << 24  # paper-regime signal length (chip_smoke phase a)
N_SERVE = 1 << 20  # served signal length (chip_smoke phase c)


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes
    ]
    return jax.jit(fn).lower(*args).compile().as_text()


F32, C64 = jnp.float32, jnp.complex64


@pytest.mark.parametrize(
    "batch,n,pty_batched",
    [
        ((), N_PAPER, False),  # solo solve
        ((4,), N_PAPER, False),  # paper-regime batch, shared P^T y
        ((8,), N_SERVE, True),  # serving slots, per-signal P^T y
        ((4,), N_SERVE + 3, False),  # a length off the block grid
    ],
)
def test_cpadmm_tail_compiles(one_chip, batch, n, pty_batched):
    sig = (batch + (n,), F32)
    scalar = ((), F32)
    text = _compiled_text(
        lambda x, cx, d, pty, mu, nu, r, g, t1, t2: fused_cpadmm_tail(
            x, cx, d, pty, mu, nu, r, g, t1, t2, interpret=False),
        one_chip, sig, sig, ((n,), F32), sig if pty_batched else ((n,), F32),
        sig, sig, scalar, scalar, scalar, scalar,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "batch,nf",
    [
        ((), N_PAPER // 2 + 1),  # half spectrum of even n: odd length
        ((4,), N_PAPER // 2 + 1),
        ((8,), N_SERVE // 2 + 1),
    ],
)
def test_spectral_pointwise_compiles(one_chip, batch, nf):
    scalar = ((), F32)
    text = _compiled_text(
        lambda c, b, vm, zn, r, s: spectral_update(c, b, vm, zn, r, s,
                                                   interpret=False),
        one_chip, ((nf,), C64), ((nf,), F32), (batch + (nf,), C64),
        (batch + (nf,), C64), scalar, scalar,
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("update", ["ista", "admm"])
def test_soft_threshold_compiles(one_chip, update):
    vec, scalar = ((N_SERVE,), F32), ((), F32)
    if update == "ista":
        text = _compiled_text(
            lambda x, d, g: fused_ista_update(x, d, g, interpret=False),
            one_chip, vec, vec, scalar,
        )
    else:
        text = _compiled_text(
            lambda x, nu, g, t: fused_admm_update(x, nu, g, t, interpret=False),
            one_chip, vec, vec, scalar, scalar,
        )
    assert "tpu_custom_call" in text


# four-step transpose chunks: a 2^20 signal over 4 chips is a
# (1024 / 4, 1024 / 2 + 1 -> padded) spectrum block per device
@pytest.mark.parametrize("shape", [(256, 516), (4, 256, 516)])
def test_wire_pack_compiles(one_chip, shape):
    pack = _compiled_text(
        lambda z: pack_wire(z, "bf16", substrate="pallas", interpret=False),
        one_chip, (shape, C64),
    )
    unpack = _compiled_text(
        lambda w: unpack_wire(w, substrate="pallas", interpret=False),
        one_chip, ((2,) + shape, jnp.bfloat16),
    )
    assert "tpu_custom_call" in pack and "tpu_custom_call" in unpack


def test_wire_pack_fp16_stays_on_xla_on_tpu(one_chip, monkeypatch):
    """The chip's compiler refuses float16 in both pack kernels, so the
    default substrate on a TPU backend routes fp16 wires through XLA's
    converts (bf16 keeps the kernel)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = (256, 516)
    f16 = _compiled_text(lambda z: pack_wire(z, "fp16"), one_chip, (shape, C64))
    f16_back = _compiled_text(
        lambda w: unpack_wire(w), one_chip, ((2,) + shape, jnp.float16))
    bf16 = _compiled_text(lambda z: pack_wire(z, "bf16"), one_chip, (shape, C64))
    assert "tpu_custom_call" not in f16 and "tpu_custom_call" not in f16_back
    assert "tpu_custom_call" in bf16


@pytest.mark.parametrize(
    "batch,n",
    [
        ((8,), N_SERVE),  # serving slots: c64[8, 2^19 + 1] -> f32[8, 2^20]
        ((1,), N_PAPER),  # paper regime: c64[1, 2^23 + 1] -> f32[1, 2^24]
    ],
)
def test_irfft_has_no_lane_reverse(one_chip, batch, n):
    """The chip's lowering of jnp.fft.irfft rebuilds the mirrored half
    spectrum with two lane-axis reverses, which run far below the memory
    roofline; the one-sided inverse runs the same complex FFT without
    them."""
    text = _compiled_text(lambda x: irfft(x, n), one_chip,
                          (batch + (n // 2 + 1,), C64))
    assert "convolution(" in text  # the chip's FFT is there
    assert "reverse(" not in text

"""Wire pack/unpack entry points: shape plumbing + substrate dispatch.

``pack_wire`` / ``unpack_wire`` are what the distributed transforms call
around every transpose all-to-all (repro.dist.fft._fwd_transpose /
_inv_transpose).  The payload is an arbitrary-rank complex chunk; packing
stacks demoted (re, im) planes on a new leading axis so the collective's
split/concat axes (trailing) shift by one and nothing else changes.

Substrates:

    'jnp'     pure-jnp cast path (XLA fuses it into the chunk producer)
    'pallas'  the kernels in kernel.py — one fused VMEM pass per direction
    'auto'    'pallas' compiled on TPU, 'jnp' elsewhere (interpret-mode
              Pallas inside every collective would be pure overhead on the
              CPU test path; the kernel parity tests force 'pallas') — and
              'jnp' for fp16 wires on TPU too: Mosaic has no float16 vector
              layout on v5e, so the compiler refuses both kernels there
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .kernel import pack_wire_pallas, unpack_wire_pallas
from .ref import pack_wire_ref, unpack_wire_ref

# the wire_dtype= plan-knob vocabulary — THE mapping every layer shares
# (PlanConfig.validate, dist.fft, tune's candidate space, the CLI flag)
WIRE_DTYPES = {
    "fp32": jnp.float32,
    "bf16": jnp.bfloat16,
    "fp16": jnp.float16,
}


def wire_itemsize(wire_dtype: str) -> int:
    """Bytes per real wire element (a complex payload element is 2x this)."""
    return jnp.dtype(WIRE_DTYPES[wire_dtype]).itemsize


def interpret_default() -> bool:
    """Pallas execution-mode default (repo-wide kernel convention):
    compiled for real on TPU, interpret mode elsewhere."""
    return jax.default_backend() != "tpu"


def _resolve(substrate: str, wire_dtype) -> str:
    if substrate == "auto":
        on_tpu = jax.default_backend() == "tpu"
        return "pallas" if on_tpu and wire_dtype != jnp.float16 else "jnp"
    if substrate not in ("jnp", "pallas"):
        raise ValueError(
            f"wire pack substrate must be 'auto', 'jnp' or 'pallas', "
            f"got {substrate!r}"
        )
    return substrate


def pack_wire(z, wire_dtype: str, substrate: str = "auto", interpret=None):
    """Complex payload (...,) -> (2, ...) split-complex wire planes.

    ``wire_dtype`` is a :data:`WIRE_DTYPES` key; 'fp32' still packs (the
    collective needs the real layout either way the caller chose this path)
    but demotes nothing.
    """
    dt = WIRE_DTYPES[wire_dtype]
    if _resolve(substrate, dt) == "jnp":
        return pack_wire_ref(z, dt)
    shape = z.shape
    L = 1
    for s in shape:
        L *= s
    re = jnp.real(z).astype(jnp.float32).reshape(L)
    im = jnp.imag(z).astype(jnp.float32).reshape(L)
    w = pack_wire_pallas(
        re, im, wire_dtype=dt,
        interpret=interpret_default() if interpret is None else interpret,
    )
    return w.reshape((2,) + shape)


def unpack_wire(w, out_dtype=jnp.complex64, substrate: str = "auto",
                interpret=None):
    """(2, ...) wire planes -> complex payload, promoted via float32."""
    if _resolve(substrate, w.dtype) == "jnp":
        return unpack_wire_ref(w, out_dtype)
    shape = w.shape[1:]
    L = 1
    for s in shape:
        L *= s
    re, im = unpack_wire_pallas(
        w.reshape(2, L),
        interpret=interpret_default() if interpret is None else interpret,
    )
    return lax.complex(re, im).astype(out_dtype).reshape(shape)

"""Hierarchical two-stage transpose: flat vs hier rows, per-tier wire bytes.

For the flat exchange and the hierarchical one (degenerate 1x1 (host,
device) mesh in-process — the collectives are free, so measured time
isolates the reshuffle/slice overhead the two-stage path adds) this times
one planned rfft matvec round and reports, per row,

  * the measured per-call time and the relative error vs the flat fp32
    path (zero for fp32 wires — the hier exchange is bit-exact);
  * the modeled production per-tier wire bytes per matvec at the cs_dryrun
    multi-host shape (n=4096^2 over H=2 hosts x D=8 devices): intra-host
    bytes ride ICI, and only the (H-1)/H cross-boundary fraction rides DCN
    — the flat row pays DCN for every byte (launch/roofline dcn_bw model).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.wire_pack.ops import wire_itemsize

from .common import emit, pick, time_fn

N1, N2 = pick((256, 256), (16, 16))
OVERLAPS = pick((1, 4), (1, 2))

# production multi-host shape (mirrors launch/cs_dryrun's mh_* variants)
PROD_N1 = PROD_N2 = 4096
PROD_H, PROD_D = 2, 8
PROD_P = PROD_H * PROD_D


def _prod_tier_bytes(hier: bool, wire: str, inter_wire: str):
    """(ici_bytes, dcn_bytes) of one production matvec (fwd + inv
    transpose) per device.  Flat: one monolithic all-to-all whose every
    byte crosses the host boundary.  Hier: the full payload intra-host at
    ``wire`` plus the (H-1)/H cross-host fraction at ``inter_wire``."""
    nf_pad = -(-(PROD_N2 // 2 + 1) // PROD_P) * PROD_P
    elems = 2 * (PROD_N1 // PROD_P) * nf_pad  # both transposes
    if not hier:
        return 0, elems * 2 * wire_itemsize(wire)
    intra = elems * 2 * wire_itemsize(wire)
    inter = elems * (PROD_H - 1) // PROD_H * 2 * wire_itemsize(inter_wire)
    return intra, inter


def main() -> None:
    from repro.dist.compat import make_hier_mesh, make_mesh
    from repro.dist.fft import (
        layout_2d,
        make_distributed_matvec,
        make_distributed_rfft,
    )

    flat_mesh = make_mesh((1,), ("model",))
    hier_mesh = make_hier_mesh(1, 1, 1)
    n = N1 * N2
    x2d = layout_2d(jax.random.normal(jax.random.PRNGKey(0), (n,)), N1, N2)
    col2d = layout_2d(
        jax.random.normal(jax.random.PRNGKey(1), (n,)) / jnp.sqrt(n), N1, N2
    )
    spec_half = make_distributed_rfft(flat_mesh, N1, N2)[0](col2d)

    rows = (  # (tag, hier, wire, inter_wire)
        ("flat_fp32", False, "fp32", "fp32"),
        ("flat_bf16", False, "bf16", "fp32"),
        ("hier_fp32", True, "fp32", "fp32"),
        ("hier_inter_bf16", True, "fp32", "bf16"),
        ("hier_bf16", True, "bf16", "bf16"),
    )
    ref = None
    for k in OVERLAPS:
        for tag, hier, wire, inter in rows:
            if hier:
                mv = make_distributed_matvec(
                    hier_mesh, rfft=True, overlap=k, wire_dtype=wire,
                    axis_name=("host", "device"), hier=True,
                    inter_wire_dtype=inter,
                )
            else:
                mv = make_distributed_matvec(
                    flat_mesh, rfft=True, overlap=k, wire_dtype=wire
                )
            t = time_fn(mv, spec_half, x2d)
            out = mv(spec_half, x2d)
            if tag == "flat_fp32" and k == OVERLAPS[0]:
                ref = out
            rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
            ici, dcn = _prod_tier_bytes(hier, wire, inter)
            emit(
                f"hier_{tag}_n{n}_k{k}",
                t,
                f"prod_ici_mb_per_matvec={ici / 1e6:.1f};"
                f"prod_dcn_mb_per_matvec={dcn / 1e6:.1f};"
                f"rel_err_vs_flat_fp32={rel:.2e}",
            )


if __name__ == "__main__":
    main()

"""Roofline derivation from the dry-run artifacts (assignment §ROOFLINE).

Hardware model: one entry of :data:`PEAKS`, keyed by the device kind JAX
reports (``jax.devices()[0].device_kind``); a kind with no entry is an
error, never a default.

    compute term    = flops_per_device / peaks.flops
    memory term     = hbm_bytes_per_device / peaks.hbm_bw
    collective term = wire_bytes_per_device / peaks.ici_bw

flops/bytes come from the trip-count-aware HLO walk (launch/hlo_analysis.py —
XLA's own cost_analysis counts while bodies once, see that module's header).
Wire bytes apply per-op multipliers for ring algorithms: all-reduce moves
2(d-1)/d ~ 2x its payload, all-gather/reduce-scatter/all-to-all ~ 1x, with
the result-shape payload parsed per op.  Payload bytes use the operand's
*own* dtype itemsize (hlo_analysis.DTYPE_BYTES), so wire-compressed
collectives (``wire_dtype='bf16'``/``'fp16'`` plans, whose transpose
payloads cross as 2-byte planes) are modeled at their true wire size with
no special-casing here.

The collective term is two-tier: bytes that cross a host boundary ride the
datacenter network at ``dcn_bw`` instead of ICI, so callers pass the
cross-host fraction as ``model_block_times(..., dcn_bytes=...)`` and the
term splits into ``ici_collective_s + dcn_collective_s`` (``peaks.dcn_bw``).  Hierarchical
plans (``hier_axes=``, repro.dist.fft) put exactly the inter-host hop into
``collective-permute`` ops, so their DCN bytes are read straight off the
HLO walk; a flat all-to-all spanning hosts charges its whole payload to
DCN.  With ``dcn_bytes=0`` (the default) the model reduces bit-for-bit to
the single-fabric numbers, keeping pre-split tune-cache entries and
``baseline_smoke.json`` valid until regenerated.

    python -m repro.launch.roofline [--dir artifacts/dryrun] [--md]
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
from typing import List


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Per-chip peak rates of one device kind (bytes and ops per second)."""

    flops: float  # bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s
    ici_bw: float  # chip-to-chip bytes/s per link
    dcn_bw: float  # host-to-host bytes/s per link (modelled, see below)


# Source: Google Cloud TPU documentation, "TPU v5e": 197 TFLOP/s bf16,
# 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect (4 links
# -> 50 GB/s each).  The DCN rate is a model, not a published peak: ~100
# Gb/s NIC per chip pair on a slice boundary -> 12.5 GB/s, derated 2x for
# the all-to-all incast pattern.
PEAKS = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9, dcn_bw=6.25e9),
}
V5E = "TPU v5 lite"


def peaks_for(device_kind: str) -> Peaks:
    """The :data:`PEAKS` entry for ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak rates for device kind {device_kind!r}; known kinds: "
            f"{sorted(PEAKS)}"
        ) from None


WIRE_MULT = {
    "all-reduce": 2.0,  # ring: reduce-scatter + all-gather
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}

# tokens per cell for MODEL_FLOPS = 6 N D (D = tokens processed per step)
from repro.configs.registry import SHAPES  # noqa: E402


def model_block_times(
    cost, overlap: int = 1, dcn_bytes: float = 0.0, *, peaks: Peaks
) -> dict:
    """Roofline terms + the hidden-collective overlap model for one compiled
    block, from a :class:`repro.launch.hlo_analysis.Cost`.

    The shared scoring core of ``launch/cs_dryrun.py`` (the dry-run tables)
    and ``ops/tune.py`` (candidate ranking) — one cost model, two callers.

    ``peaks`` is the :data:`PEAKS` entry of the device the block runs on.
    ``dcn_bytes`` is the portion of the wire bytes that crosses a host
    boundary and therefore rides ``peaks.dcn_bw`` instead of ``ici_bw`` (clamped
    to the total — a caller can pass raw HLO collective-permute bytes
    without worrying about multipliers).  The default 0.0 subtracts and
    adds exact float zeros, so single-fabric scores are reproduced
    bit-for-bit.

    Overlap model: with the transpose split into K chunks, chunk i's
    collective flies while chunk i+1's first-stage FFT+twiddle runs, so at
    most (K-1)/K of the wire time can hide — and never more than the
    first-stage local-work window itself (~half the per-iteration local
    time; the column FFT after the transpose is the other half and cannot
    overlap its own transform's collective).  Local FFTs lower to custom
    calls whose flops XLA's cost walk cannot see, but at production shapes
    they are HBM-bound anyway, so the window is bounded by the larger of
    the compute and memory terms.
    """
    wire = sum(
        WIRE_MULT.get(op, 1.0) * b for op, b in cost.collective_bytes.items()
    )
    compute_s = cost.flops / peaks.flops
    memory_s = cost.bytes / peaks.hbm_bw
    dcn_wire = min(float(dcn_bytes), wire)
    ici_s = (wire - dcn_wire) / peaks.ici_bw
    dcn_s = dcn_wire / peaks.dcn_bw
    collective_s = ici_s + dcn_s
    local_s = max(compute_s, memory_s)
    hidden_s = min((overlap - 1) / overlap * collective_s, 0.5 * local_s)
    effective_s = collective_s - hidden_s
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "ici_collective_s": ici_s,
        "dcn_collective_s": dcn_s,
        "dcn_bytes": dcn_wire,
        "overlap": overlap,
        "hidden_collective_s": hidden_s,
        "hidden_collective_frac": hidden_s / collective_s if collective_s else 0.0,
        "effective_collective_s": effective_s,
        "modeled_total_s": local_s + effective_s,
    }


def model_flops(rec: dict) -> float:
    seq, batch, kind = SHAPES[rec["shape"]]
    n_active = rec["params"]["active"]
    if kind == "train":
        tokens = seq * batch
        return 6.0 * n_active * tokens
    if kind == "prefill":
        tokens = seq * batch
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * batch


def derive(rec: dict) -> dict:
    """Roofline terms of one dry-run cell; the dry-runs model a v5e pod."""
    peaks = PEAKS[V5E]
    w = rec["hlo_walk"]
    n_dev = rec["n_devices"]
    compute_s = w["flops"] / peaks.flops
    memory_s = w["bytes"] / peaks.hbm_bw
    wire = sum(
        WIRE_MULT.get(op, 1.0) * b for op, b in w["collective_bytes"].items()
    )
    collective_s = wire / peaks.ici_bw
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(rec)
    hlo_global = w["flops"] * n_dev
    mem = rec.get("memory_analysis", {})
    hbm_need = (
        mem.get("argument_size_in_bytes", 0)
        + mem.get("temp_size_in_bytes", 0)
        + mem.get("output_size_in_bytes", 0)
        - mem.get("alias_size_in_bytes", 0)
    )
    return {
        **{k: rec[k] for k in ("arch", "shape", "mesh", "kind", "n_devices")},
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": bottleneck,
        "step_s_bound": max(terms.values()),
        "roofline_fraction": compute_s / max(terms.values()),
        "model_flops": mf,
        "hlo_flops_global": hlo_global,
        "useful_ratio": mf / hlo_global if hlo_global else 0.0,
        "hbm_need_bytes": hbm_need,
        "fits_16g": hbm_need <= 16e9,
        "collective_detail": w["collective_bytes"],
    }


def fmt_s(x: float) -> str:
    if x >= 1:
        return f"{x:.2f}s"
    if x >= 1e-3:
        return f"{x*1e3:.1f}ms"
    return f"{x*1e6:.0f}us"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="artifacts/dryrun")
    ap.add_argument("--mesh", default="single", help="single|multipod|all")
    ap.add_argument("--json-out", default="artifacts/roofline.json")
    args = ap.parse_args()

    rows: List[dict] = []
    for path in sorted(glob.glob(os.path.join(args.dir, "*.json"))):
        rec = json.load(open(path))
        if not rec.get("ok"):
            rows.append({k: rec.get(k) for k in ("arch", "shape", "mesh")} | {"error": True})
            continue
        if args.mesh != "all" and rec["mesh"] != args.mesh:
            continue
        rows.append(derive(rec))

    rows.sort(key=lambda r: (r.get("arch", ""), r.get("shape", "")))
    os.makedirs(os.path.dirname(args.json_out), exist_ok=True)
    with open(args.json_out, "w") as f:
        json.dump(rows, f, indent=1)

    hdr = (
        "| arch | shape | compute | memory | collective | bound | roofline frac "
        "| useful (6ND/HLO) | HBM need/dev | fits 16G |"
    )
    print(hdr)
    print("|" + "---|" * 10)
    for r in rows:
        if r.get("error"):
            print(f"| {r['arch']} | {r['shape']} | ERROR |")
            continue
        print(
            f"| {r['arch']} | {r['shape']} | {fmt_s(r['compute_s'])} | "
            f"{fmt_s(r['memory_s'])} | {fmt_s(r['collective_s'])} | "
            f"**{r['bottleneck']}** | {r['roofline_fraction']*100:.0f}% | "
            f"{min(r['useful_ratio'],99):.2f} | {r['hbm_need_bytes']/1e9:.1f}GB | "
            f"{'Y' if r['fits_16g'] else 'N'} |"
        )


if __name__ == "__main__":
    main()

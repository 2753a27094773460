import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

"""Dry-run + roofline for the paper's own workload on the production mesh.

Lowers one CPADMM iteration-block (50 iterations, as the recovery launcher
runs it) for a batch of large signals: each signal sharded over the model
axis, the batch sharded over (pod) x data — the cluster-job form of the
paper's Sec. 7 deblurring.  Four variants of the iteration are compared:

    baseline    paper-faithful 6-transform iteration, full complex spectra
                (6 all-to-alls per iteration)
    fused       frequency-domain x-update + stacked transforms
                (2 all-to-alls per iteration, see dist/recovery.py)
    fused_rfft  fused + half-spectrum (rfft) transforms: same all-to-all
                count, ~2x lower local FFT flops and all-to-all wire bytes
                per signal (see dist/fft.py)
    overlap     fused_rfft with overlap=K chunked transposes: each
                transform's all-to-all is split into K chunk collectives
                issued as their first-stage FFT finishes, so up to
                (K-1)/K of the wire time hides behind local compute
                (same payload on the wire, zero-padded to equal chunks when
                K does not divide the chunked extent — the win is latency,
                reported as
                the hidden-collective fraction / effective collective time)
    wire_bf16   overlap with wire_dtype='bf16': every chunk payload demoted
                to split-complex bf16 planes right before its collective
                (dist/fft wire packing), halving the bytes that actually
                cross the wire — the modeled collective bytes come from the
                compiled HLO, so the table reflects the true wire dtype

A second, multi-host section compares the same best-lever iteration on a
``data x host x device`` mesh (compat.make_hier_mesh), where the transform
axis spans hosts and every cross-host byte rides DCN instead of ICI:

    mh_flat     wire_bf16 lowered over the factored (host, device) axis as
                one monolithic all-to-all — every transpose byte crosses
                the host boundary and is charged at the DCN rate
    mh_hier     the two-stage hierarchical exchange (hier_axes=(H, D),
                dist/fft): full payload intra-host on ICI, only the
                (H-1)/H cross-boundary fraction on DCN as collective-
                permutes, with its own inter_wire_dtype

    per-tier bytes are read off the compiled HLO (collective-permute = the
    DCN hop), and the two-tier model (roofline PEAKS dcn_bw) scores both.

This is the §Perf hillclimb cell for the paper's technique: the printed
per-signal FFT-flop and wire-byte ratios are the measured value of each
lever, and the JSON artifact pins them per push.

    PYTHONPATH=src python -m repro.launch.cs_dryrun [--n1 4096 --n2 4096]
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.dist.compat import make_hier_mesh
from repro.dist.fft import padded_rfft_len
from repro.dist.recovery import DistCpadmmState
from repro.launch.hlo_analysis import analyze_compiled
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import PEAKS, V5E, model_block_times
from repro.ops import plan_from_parts
from repro.ops.plan import _transform_extent

SDS = jax.ShapeDtypeStruct

VARIANTS = (  # (tag, fused, rfft, overlap, wire_dtype)
    ("baseline", False, False, 1, "fp32"),
    ("fused", True, False, 1, "fp32"),
    ("fused_rfft", True, True, 1, "fp32"),
    ("overlap", True, True, 4, "fp32"),
    ("wire_bf16", True, True, 4, "bf16"),
)


def lower_variant(
    mesh, n1, n2, batch, iters, fused, rfft=False, overlap=1,
    wire_dtype="fp32", axis_name="model", hier_axes=None,
    inter_wire_dtype="fp32",
):
    """Lower one iteration block through the plan API's abstract entry point
    (``ExecutionPlan.cpadmm_block``): the batch rides (pod x) data, each
    signal's transforms shard over the model axis (or the factored
    ``(host, device)`` pair) — the same lowering the unified drivers
    execute, here compiled from ShapeDtypeStructs only."""
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    pl = plan_from_parts(
        mesh, n1=n1, n2=n2, rfft=rfft, overlap=overlap, fused=fused,
        batch_axis=dp, axis_name=axis_name, wire_dtype=wire_dtype,
        hier_axes=hier_axes, inter_wire_dtype=inter_wire_dtype,
    )
    block = pl.cpadmm_block(iters)
    model_size = _transform_extent(mesh, pl.axis_name)
    ncols = padded_rfft_len(n2, model_size) if rfft else n2
    spec_s = SDS((n1, ncols), jnp.complex64)
    diag_s = SDS((n1, n2), jnp.float32)
    real_b = SDS((batch, n1, n2), jnp.float32)
    state_s = DistCpadmmState(*(real_b,) * 5)
    return block.lower(spec_s, spec_s, diag_s, real_b, state_s).compile()


def analyze(compiled, iters, batch, overlap=1, dcn="none"):
    # The roofline terms and the hidden-collective overlap model live in
    # launch/roofline.model_block_times — shared with the autotuner's
    # candidate scoring (ops/tune.py) so the dry-run tables and the tuner
    # can never drift apart.  ``dcn`` names which collective crosses hosts
    # (tune._dcn_bytes policy): "permute" for hierarchical plans (exactly
    # the inter-host hop), "all" for a flat exchange spanning hosts (every
    # transpose byte), "none" for single-fabric meshes.
    c = analyze_compiled(compiled)
    a2a_bytes = c.collective_bytes.get("all-to-all", 0)
    cp_bytes = c.collective_bytes.get("collective-permute", 0)
    dcn_bytes = {"none": 0.0, "permute": float(cp_bytes),
                 "all": float(a2a_bytes)}[dcn]
    # the dry-run models the v5e production pod, whatever compiles it
    times = model_block_times(c, overlap, dcn_bytes=dcn_bytes, peaks=PEAKS[V5E])
    return {
        "flops_per_dev": c.flops,
        "bytes_per_dev": c.bytes,
        "collective_bytes_per_dev": c.collective_bytes,
        "collective_counts": {k: v for k, v in c.collective_counts.items()},
        **times,
        "per_iter_a2a": c.collective_counts.get("all-to-all", 0) / iters,
        "flops_per_signal": c.flops / batch,
        "a2a_bytes_per_signal": a2a_bytes / batch,
        "cp_bytes_per_signal": cp_bytes / batch,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n1", type=int, default=4096)
    ap.add_argument("--n2", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--hosts", type=int, default=2,
                    help="host tier extent H of the multi-host section")
    ap.add_argument("--devices-per-host", type=int, default=8,
                    help="device tier extent D of the multi-host section")
    ap.add_argument("--no-hier", action="store_true",
                    help="skip the multi-host flat-vs-hier section")
    ap.add_argument("--out", default="artifacts/cs_dryrun.json")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multipod)
    results = {}
    for tag, fused, rfft, overlap, wire in VARIANTS:
        t0 = time.time()
        compiled = lower_variant(
            mesh, args.n1, args.n2, args.batch, args.iters, fused, rfft,
            overlap, wire,
        )
        res = analyze(compiled, args.iters, args.batch, overlap)
        res["wire_dtype"] = wire
        mem = compiled.memory_analysis()
        res["hbm_need_gb"] = (
            getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "temp_size_in_bytes", 0)
        ) / 1e9
        res["compile_s"] = round(time.time() - t0, 1)
        results[tag] = res
        dom = max(
            ("compute_s", "memory_s", "effective_collective_s"),
            key=lambda k: res[k],
        )
        print(
            f"{tag:10s} n={args.n1*args.n2} batch={args.batch}: "
            f"compute {res['compute_s']*1e3:.1f}ms  memory {res['memory_s']*1e3:.1f}ms  "
            f"collective {res['collective_s']*1e3:.1f}ms "
            f"(hidden {res['hidden_collective_frac']*100:.0f}% -> eff "
            f"{res['effective_collective_s']*1e3:.1f}ms)  bound={dom}  "
            f"a2a/iter={res['per_iter_a2a']:.1f}  HBM {res['hbm_need_gb']:.1f}GB"
        )
    b, f, r = results["baseline"], results["fused"], results["fused_rfft"]
    o, w = results["overlap"], results["wire_bf16"]
    print(
        f"fused vs baseline: collective {b['collective_s']/max(f['collective_s'],1e-12):.2f}x down, "
        f"flops {b['flops_per_dev']/max(f['flops_per_dev'],1):.2f}x down, "
        f"bytes {b['bytes_per_dev']/max(f['bytes_per_dev'],1):.2f}x down"
    )
    print(
        f"rfft vs full-complex (fused): per-signal total flops "
        f"{f['flops_per_signal']/max(r['flops_per_signal'],1):.2f}x down "
        f"(FFT-only ~2x; the elementwise tail dilutes the total), "
        f"per-signal all-to-all bytes "
        f"{f['a2a_bytes_per_signal']/max(r['a2a_bytes_per_signal'],1):.2f}x down"
    )
    print(
        f"overlap(K={o['overlap']}) vs fused_rfft: same "
        f"{o['a2a_bytes_per_signal']/1e6:.1f}MB/signal on the wire in "
        f"{o['per_iter_a2a']:.0f} chunk-collectives/iter "
        f"(was {r['per_iter_a2a']:.0f}); hidden-collective fraction "
        f"{o['hidden_collective_frac']*100:.0f}% -> effective collective "
        f"{r['collective_s']*1e3:.1f}ms -> {o['effective_collective_s']*1e3:.1f}ms "
        f"per {args.iters}-iter block"
    )
    print(
        f"wire_bf16 vs overlap(fp32 wire): per-signal all-to-all bytes "
        f"{o['a2a_bytes_per_signal']/max(w['a2a_bytes_per_signal'],1):.2f}x "
        f"down (split-complex bf16 planes, same chunk schedule); vs "
        f"fused_rfft "
        f"{r['a2a_bytes_per_signal']/max(w['a2a_bytes_per_signal'],1):.2f}x"
    )
    # a2a bytes come from the compiled HLO's operand dtypes (hlo_analysis
    # DTYPE_BYTES) — the wire dtype's true itemsize, not the spectrum dtype's
    per_sig = {
        t: {
            "flops_per_signal": results[t]["flops_per_signal"],
            "a2a_bytes_per_signal": results[t]["a2a_bytes_per_signal"],
            "effective_collective_s": results[t]["effective_collective_s"],
            "wire_dtype": results[t]["wire_dtype"],
        }
        for t, *_ in VARIANTS
    }
    print("per-signal wire/flop table:")
    for t, row in per_sig.items():
        print(
            f"  {t:10s} flops {row['flops_per_signal']/1e9:8.2f}G  "
            f"a2a {row['a2a_bytes_per_signal']/1e6:7.1f}MB  "
            f"eff-collective {row['effective_collective_s']*1e3:6.1f}ms  "
            f"wire={row['wire_dtype']}"
        )

    if not args.no_hier:
        # multi-host section: same best-lever iteration (fused rfft, K=4,
        # bf16 wires), transform axis factored over (host, device) so the
        # flat exchange pays DCN for every byte and the hierarchical one
        # only for the cross-boundary (H-1)/H fraction
        H, D = args.hosts, args.devices_per_host
        data = args.batch  # one data shard per signal, as in production
        mesh_h = make_hier_mesh(data, H, D)
        mh = [
            ("mh_flat", None, "fp32", "all"),
            ("mh_hier", (H, D), "bf16", "permute"),
        ]
        for tag, hier, iw, dcn in mh:
            t0 = time.time()
            compiled = lower_variant(
                mesh_h, args.n1, args.n2, args.batch, args.iters,
                fused=True, rfft=True, overlap=4, wire_dtype="bf16",
                axis_name=("host", "device"), hier_axes=hier,
                inter_wire_dtype=iw,
            )
            res = analyze(compiled, args.iters, args.batch, 4, dcn=dcn)
            res["wire_dtype"] = "bf16"
            res["inter_wire_dtype"] = iw
            res["hier_axes"] = list(hier) if hier else None
            res["compile_s"] = round(time.time() - t0, 1)
            results[tag] = res
            print(
                f"{tag:10s} mesh=data{data} x host{H} x device{D}: "
                f"ICI {res['ici_collective_s']*1e3:.1f}ms + DCN "
                f"{res['dcn_collective_s']*1e3:.1f}ms = collective "
                f"{res['collective_s']*1e3:.1f}ms  per-signal a2a "
                f"{res['a2a_bytes_per_signal']/1e6:.1f}MB / inter-host "
                f"{(res['dcn_bytes']/args.batch)/1e6:.1f}MB"
            )
        fl, hi = results["mh_flat"], results["mh_hier"]
        print(
            f"hier vs flat over {H} hosts: inter-host bytes "
            f"{fl['dcn_bytes']/max(hi['dcn_bytes'],1):.2f}x down "
            f"((H-1)/H of the payload crosses, demoted to "
            f"{hi['inter_wire_dtype']}), modeled collective "
            f"{fl['collective_s']/max(hi['collective_s'],1e-12):.2f}x down, "
            f"modeled block "
            f"{fl['modeled_total_s']/max(hi['modeled_total_s'],1e-12):.2f}x down"
        )

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    json.dump(
        {"n1": args.n1, "n2": args.n2, "batch": args.batch,
         "mesh": "multipod" if args.multipod else "single", **results},
        open(args.out, "w"), indent=1,
    )


if __name__ == "__main__":
    main()

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The statements above MUST stay first — jax locks the device count on first
init, and the production meshes need 512 placeholder devices.  Run as

    PYTHONPATH=src python -m repro.launch.dryrun --arch codeqwen1.5-7b \
        --shape train_4k --mesh single            # one cell
    PYTHONPATH=src python -m repro.launch.dryrun --all --jobs 8  # everything

Each cell records, into artifacts/dryrun/<arch>__<shape>__<mesh>.json:
    * compiled.memory_analysis()   (bytes per device — "proves it fits")
    * compiled.cost_analysis()     (FLOPs / bytes for §Roofline)
    * per-collective byte counts parsed from the optimized HLO
    * the sharding-rule fallbacks that were applied
Cells are independent; --all fans them out over worker subprocesses.
"""

import argparse
import json
import re
import subprocess
import sys
import time
from typing import Dict

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "c64": 8, "c128": 16,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_RE = re.compile(
    r"(\w[\w.-]*)\s*=\s*(\w+)\[\]?.*?\b"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)


def _shape_bytes(shape_str: str) -> int:
    """'f32[16,128]{1,0}' -> byte count (0 for tuples handled by caller)."""
    m = re.match(r"(\w+)\[([\d,]*)\]", shape_str)
    if not m:
        return 0
    dt, dims = m.groups()
    nbytes = DTYPE_BYTES.get(dt, 4)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * nbytes


def parse_collective_bytes(hlo: str) -> Dict[str, int]:
    """Sum output-shape bytes of every collective op in optimized HLO text.

    Uses the *result* shape of each collective instruction (per-device
    payload).  Tuples (e.g. fused all-reduces) are expanded element-wise.
    """
    out: Dict[str, int] = {}
    for line in hlo.splitlines():
        line = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.-]+\s*=\s*(.+?)\s+(all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)", line)
        if not m:
            continue
        shape_str, op = m.groups()
        total = sum(_shape_bytes(s) for s in re.findall(r"\w+\[[\d,]*\]", shape_str))
        out[op] = out.get(op, 0) + total
        out.setdefault(f"{op}_count", 0)
        out[f"{op}_count"] += 1
    return out


def run_cell(arch: str, shape: str, mesh_kind: str, out_path: str) -> dict:
    import jax

    from repro.configs.registry import full_config
    from repro.dist.sharding import DEFAULT_RULES, activate_rules, rules_for_arch
    from repro.launch import partition
    from repro.launch.mesh import make_production_mesh
    from repro.launch.specs import cell_specs

    t0 = time.time()
    cfg = full_config(arch)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    rules = rules_for_arch(cfg, mesh)

    kind, fn, args = cell_specs(cfg, shape)
    if kind == "train":
        state_specs, batch_specs = args
        in_sh = (
            partition.train_state_shardings(mesh, state_specs, rules),
            partition.batch_shardings(mesh, batch_specs, rules),
        )
    elif kind == "prefill":
        params_specs_, batch_specs = args
        in_sh = (
            partition.param_shardings(mesh, params_specs_, rules),
            partition.batch_shardings(mesh, batch_specs, rules),
        )
    else:  # decode
        params_specs_, tok_specs, state_specs = args
        in_sh = (
            partition.param_shardings(mesh, params_specs_, rules),
            partition.batch_shardings(mesh, tok_specs, rules),
            partition.cache_shardings(mesh, state_specs, rules),
        )

    with activate_rules(rules, mesh):
        jitted = jax.jit(fn, in_shardings=in_sh)
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    mem_dict = {}
    for field in (
        "generated_code_size_in_bytes",
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
    ):
        v = getattr(mem, field, None)
        if v is not None:
            mem_dict[field] = int(v)

    cost = compiled.cost_analysis() or {}
    cost_dict = {
        k: float(v)
        for k, v in cost.items()
        if isinstance(v, (int, float)) and k in ("flops", "bytes accessed", "transcendentals", "optimal_seconds")
        or str(k).startswith("bytes accessed")
    }

    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)  # raw, trip-count-naive (debug)

    from repro.launch.hlo_analysis import analyze_hlo

    walked = analyze_hlo(hlo)  # trip-count-aware per-device cost

    from repro.models.config import count_params

    result = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_kind,
        "mesh_shape": list(mesh.devices.shape),
        "n_devices": int(mesh.devices.size),
        "kind": kind,
        "rules_fallbacks": {
            k: v for k, v in rules.items() if v != DEFAULT_RULES.get(k)
        },
        "memory_analysis": mem_dict,
        "cost_analysis": cost_dict,
        "hlo_walk": {
            "flops": walked.flops,
            "bytes": walked.bytes,
            "transcendentals": walked.transcendentals,
            "collective_bytes": walked.collective_bytes,
            "collective_counts": walked.collective_counts,
        },
        "collectives_raw": coll,
        "params": count_params(cfg),
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "ok": True,
    }
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(result, f, indent=1)
    return result


def all_cells():
    from repro.configs.registry import all_arch_ids, cells_for

    for arch in all_arch_ids():
        for shape in cells_for(arch):
            for mesh_kind in ("single", "multipod"):
                yield arch, shape, mesh_kind


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multipod"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=6)
    ap.add_argument("--out-dir", default=os.path.abspath(ARTIFACT_DIR))
    ap.add_argument("--only-missing", action="store_true")
    args = ap.parse_args()

    if not args.all:
        assert args.arch and args.shape
        out = os.path.join(args.out_dir, f"{args.arch}__{args.shape}__{args.mesh}.json")
        try:
            res = run_cell(args.arch, args.shape, args.mesh, out)
            print(json.dumps(res, indent=1))
        except Exception as e:  # record the failure for the aggregate table
            os.makedirs(args.out_dir, exist_ok=True)
            with open(out, "w") as f:
                json.dump(
                    {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                     "ok": False, "error": repr(e)[:2000]},
                    f,
                )
            print(f"FAILED {args.arch} {args.shape} {args.mesh}: {e}", file=sys.stderr)
            sys.exit(1)
        return

    # fan out over subprocesses (each gets its own 512-device jax runtime)
    cells = list(all_cells())
    if args.only_missing:
        cells = [
            c
            for c in cells
            if not os.path.exists(os.path.join(args.out_dir, f"{c[0]}__{c[1]}__{c[2]}.json"))
        ]
    print(f"{len(cells)} cells to run, {args.jobs} workers")
    procs: list = []
    done = 0
    while cells or procs:
        while cells and len(procs) < args.jobs:
            arch, shape, mesh_kind = cells.pop(0)
            cmd = [
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                "--out-dir", args.out_dir,
            ]
            p = subprocess.Popen(
                cmd, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True
            )
            p._cell = (arch, shape, mesh_kind)  # type: ignore
            procs.append(p)
        for p in list(procs):
            if p.poll() is not None:
                procs.remove(p)
                done += 1
                status = "ok" if p.returncode == 0 else "FAIL"
                print(f"[{done}] {p._cell}: {status}", flush=True)
                if p.returncode != 0:
                    err = p.stderr.read()
                    print(err[-1500:], flush=True)
        time.sleep(2)
    print("dry-run sweep complete")


if __name__ == "__main__":
    main()

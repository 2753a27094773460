"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (paper-artifact mapping in
DESIGN.md Sec. 7).

    python -m benchmarks.run [--only <name>] [--smoke] [--json OUT.json]

``--smoke`` swaps every suite to tiny problem sizes (seconds on a CI CPU;
run-to-completion check, not perf data); ``--json`` additionally writes the
structured rows — CI uploads ``BENCH_smoke.json`` as the per-push artifact
that anchors the perf trajectory.
"""

from __future__ import annotations

import argparse
import os
import sys
import traceback

SUITE_NAMES = (
    "footprint",  # Fig. 3
    "admm_recovery",  # Fig. 4
    "ista_recovery",  # Fig. 5
    "throughput",  # Fig. 6
    "matvec",  # Fig. 7
    "error_trace",  # Fig. 8
    "deblur",  # Sec. 7 / Fig. 9
    "grad_compression",  # beyond-paper
    "batched_recovery",  # beyond-paper: data-axis batching amortization
    "overlap",  # beyond-paper: chunked-transpose overlap sweep
    "dist_ista",  # beyond-paper: plan-API distributed CPISTA/FISTA overhead
    "autotune",  # beyond-paper: cost-model plan autotuner vs hand-picked
    "serve",  # beyond-paper: continuous-batching dispatcher vs static batch
    "wire",  # beyond-paper: wire-compressed collective precision sweep
    "hier",  # beyond-paper: hierarchical two-stage transpose, per-tier bytes
    "prox",  # beyond-paper: pluggable-prior cost per solve + TV map-making
)


def _load_suites():
    """Import suite modules *after* the smoke env var is settled — their
    size constants are bound at import time via common.pick."""
    import importlib

    return {name: importlib.import_module(f"benchmarks.bench_{name}") for name in SUITE_NAMES}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="run a single suite")
    ap.add_argument("--smoke", action="store_true", help="tiny sizes (CI run-to-completion)")
    ap.add_argument("--json", default=None, help="also write rows to this JSON file")
    args = ap.parse_args()

    if args.smoke:
        os.environ["REPRO_BENCH_SMOKE"] = "1"
    from repro.launch.env import configure_compile_cache

    configure_compile_cache()
    suites = _load_suites()
    from benchmarks import common

    print("name,us_per_call,derived")
    failed = []
    for name, mod in suites.items():
        if args.only and name != args.only:
            continue
        common.CURRENT_SUITE = name  # rows emitted from here tag this suite
        try:
            mod.main()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    common.CURRENT_SUITE = None
    if args.json:
        common.write_json(args.json)
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

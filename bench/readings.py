#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip, in one process.

    python3 bench/readings.py --workload <cell> --seeds 1 2 3 ... \
        --control-seeds 101 102 103 [--seconds 3] [--traffic '{"lead_in_s": 4}']

For each seed it runs the cell as ``bench/run.py`` does (short window,
cell size) and prints the numbers compared; for each control seed it does
the same with the lower-precision reference's answers in the program's
place.  The lower reading of a number is the largest over the program's
seeds, the upper the smallest over the control's (``bench/limits/``).
The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--traffic", default="{}", help="JSON overrides of the traffic")
    a = ap.parse_args(argv)
    harness.configure_compile_cache()
    rows = {"program": {}, "control": {}}
    for kind, seeds in (("program", a.seeds), ("control", a.control_seeds)):
        for seed in seeds:
            t0 = time.perf_counter()
            r = harness.run_cell(a.workload, seed, a.seconds, False, t_start=t0,
                                 overrides={"traffic": json.loads(a.traffic)},
                                 control=kind == "control")
            checks = {k: v["value"] for k, v in r["checks"].items()}
            rows[kind][seed] = checks
            print(json.dumps({"kind": kind, "seed": seed, "checks": checks,
                              "correct": r["correct"], "metrics": r["metrics"],
                              "seconds": time.perf_counter() - t0}), flush=True)
    for kind, by_seed in rows.items():
        names = sorted({k for c in by_seed.values() for k in c})
        pick = max if kind == "program" else min
        print(json.dumps({kind: {k: pick(c[k] for c in by_seed.values() if k in c)
                                 for k in names}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

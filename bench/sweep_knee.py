#!/usr/bin/env python3
"""Find the knee of a served cell once, by a sweep of fixed rates on the chip.

    python3 bench/sweep_knee.py --workload <cell> --rates 1 1.5 2 3 [--seconds 30]

Runs the cell's traffic at each offered rate in one process (no reference
check) and prints, per rate, the requests finished per second inside the
window, the latency median and 90th percentile, the backlog left at the
window's end and the slot fill.  The knee is the highest rate whose backlog
does not grow; the cell's traffic file then fixes its rate at about four
fifths of it, as a number.
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--traffic", default="{}", help="JSON overrides of the traffic")
    a = ap.parse_args(argv)
    harness.configure_compile_cache()
    for rate in a.rates:
        over = {**json.loads(a.traffic), "rate_per_s": rate, "check_requests": 0}
        r = harness.run_cell(a.workload, a.seed, a.seconds, False,
                             t_start=time.perf_counter(), overrides={"traffic": over})
        print(json.dumps({"rate_per_s": rate, "metrics": r["metrics"],
                          "attempted": r["attempted"], "failed": r["failed"],
                          **r["counters"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

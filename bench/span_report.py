#!/usr/bin/env python3
"""Where a traced run of the served cell spends its time, by the program's
own spans and scopes: an analysis beside the benchmark, not one of its
metrics.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds 51 \
        [--out report.json]

Runs the cell once with a device trace, as ``bench/run.py --trace 1`` does,
and prints its result line first.  It keeps what the harness discards: the
trace, and the serving engine, whose round program it compiles again
(``BatchEngine.lower_round()``: the same arguments, so the compilation
cache returns the program that ran).  Then it prints, and writes to
``--out``, one JSON object:

``idle``
    the window's idle gaps of the chip, each labelled with the innermost
    host span of either kind (the benchmark's ``bench.``, the program's
    ``serve.``) at its middle: the ten longest, and count and seconds by
    label;
``spans``
    count, mean, largest and summed milliseconds of each ``serve.`` span
    inside the window;
``scopes``
    the leaf op time of the ``round_fn`` programs, split by the named
    scopes of the compiled text's ``op_name`` metadata: the share of it
    whose instruction carries an ``op_name`` at all, each scope's share of
    the whole, and the busiest instructions that carry none, with their
    HLO text (``unattributed``);
``memory``
    the round program's ``memory_analysis``.

The functions below take a ``ProfileData``, a ``trace_reduce.Summary`` and
a compiled text, so ``bench/tests/test_span_report.py`` checks them on a
hand-made trace without a chip.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import re
import shutil
import sys
import time
from typing import Dict, List, Optional, Tuple

T_START = time.perf_counter()  # set-up is timed from here, as in run.py

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

PROGRAM_PREFIX = "serve."
ROUND_MODULE = "round_fn"
SCOPES = ("transform", "cpadmm.xupdate", "cpadmm.cx", "cpadmm.tail", "until.test",
          "until.freeze")
LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
OP_NAME = re.compile(r'op_name="([^"]*)"')

Span = Tuple[str, float, float]  # (name, start_ns, end_ns)


def program_spans(profile) -> List[Span]:
    """The host plane's ``serve.`` spans, any ``#arg=...#`` suffix cut."""
    out = []
    for plane in profile.planes:
        if plane.name != trace_reduce.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PROGRAM_PREFIX):
                    out.append((ev.name.split("#")[0], ev.start_ns,
                                ev.start_ns + ev.duration_ns))
    return out


def innermost(spans: List[Span], t: float) -> str:
    """The span holding ``t`` that started last (``bench.window`` if none)."""
    inner = [(s, n) for n, s, e in spans if s <= t <= e and n != trace_reduce.WINDOW_SPAN]
    return max(inner)[1] if inner else trace_reduce.WINDOW_SPAN


def idle(summary, spans: List[Span], top: int = 10) -> dict:
    every = list(summary.spans) + spans
    gaps = [((e - s) * 1e-9, innermost(every, (s + e) / 2))
            for s, e, _ in summary.idle_gaps()]
    by: Dict[str, List[float]] = {}
    for d, lab in gaps:
        row = by.setdefault(lab, [0, 0.0])
        row[0] += 1
        row[1] += d
    return {"idle_s": sum(d for d, _ in gaps),
            "longest": [[lab, d] for d, lab in sorted(gaps, reverse=True)[:top]],
            "by_label": {k: {"count": c, "s": s} for k, (c, s) in
                         sorted(by.items(), key=lambda kv: -kv[1][1])}}


def span_ms(summary, spans: List[Span]) -> dict:
    w0, w1 = summary.window
    dur: Dict[str, List[float]] = {}
    for n, s, e in spans:
        if w0 <= s and e <= w1:
            dur.setdefault(n, []).append((e - s) * 1e-6)
    return {n: {"count": len(v), "mean": sum(v) / len(v), "max": max(v), "sum": sum(v)}
            for n, v in sorted(dur.items())}


def instructions(text: str) -> Dict[str, Tuple[Optional[str], str]]:
    """Each instruction of a compiled HLO text: its ``op_name`` (None when
    it carries none) and its line."""
    out = {}
    for line in text.splitlines():
        m = LINE.match(line)
        if m:
            n = OP_NAME.search(line)
            out[m.group(1)] = (n.group(1) if n else None, line.strip())
    return out


def scopes(summary, text: str, top: int = 12, device: int = 0) -> dict:
    """Leaf op time of the round programs by the scopes of ``text``."""
    instr = instructions(text)
    d = summary.devices[device]
    rounds = [(s, e) for n, s, e in d.modules if ROUND_MODULE in n]
    per: Dict[str, float] = {}
    for n, s, e in d.ops:
        if any(rs <= (s + e) / 2 <= re_ for rs, re_ in rounds):
            per[n] = per.get(n, 0.0) + (e - s) * 1e-9
    total = sum(per.values())
    if not total:
        return {"round_op_s": 0.0}
    named = {n: instr.get(n, (None, ""))[0] for n in per}
    share = {sc: 100.0 * sum(t for n, t in per.items()
                             if sc in (named[n] or "").split("/")) / total
             for sc in SCOPES}
    bare = sorted(((t, n) for n, t in per.items() if named[n] is None), reverse=True)
    return {"round_op_s": total,
            "named_share": 100.0 * sum(t for n, t in per.items() if named[n]) / total,
            "scope_share": share,
            "unattributed": [{"op": n, "s": t, "in_text": n in instr,
                              "hlo": instr.get(n, (None, ""))[1][:300]}
                             for t, n in bare[:top]]}


@contextlib.contextmanager
def keeping(trace_copy: str, engines: list):
    """While the harness runs: copy the trace before it is reduced and
    deleted, and keep each server's engines past the driver's end."""
    from repro.serve import RecoveryServer

    reduce_dir, warmup = trace_reduce.reduce_dir, RecoveryServer.warmup

    def keep_trace(d, ids=None):
        shutil.rmtree(trace_copy, ignore_errors=True)
        shutil.copytree(d, trace_copy)
        return reduce_dir(d, ids)

    def keep_server(self, req):
        warmup(self, req)
        engines.extend(self.engines.values())

    trace_reduce.reduce_dir, RecoveryServer.warmup = keep_trace, keep_server
    try:
        yield
    finally:
        trace_reduce.reduce_dir, RecoveryServer.warmup = reduce_dir, warmup


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    harness.configure_compile_cache()
    from jax.profiler import ProfileData

    copy = os.path.join(harness.CACHE_DIR, "span_report_trace")
    engines: list = []
    try:
        with keeping(copy, engines):
            result = harness.run_cell(args.workload, args.seed, args.seconds, True,
                                      t_start=T_START)
    except harness.NoChip as e:
        harness.log(f"bench: {e}")
        return 3
    print(json.dumps(result), flush=True)

    (path,) = glob.glob(os.path.join(copy, "**", "*.xplane.pb"), recursive=True)
    profile = ProfileData.from_file(path)
    devices = [d.id for d in harness.require_chips(1)[:1]]
    summary = trace_reduce.reduce(profile, devices)
    spans = program_spans(profile)
    report = {"workload": args.workload, "seed": args.seed,
              "idle": idle(summary, spans), "spans": span_ms(summary, spans)}
    if len(engines) == 1:
        compiled = engines[0].lower_round().compile()
        m = compiled.memory_analysis()
        report["scopes"] = scopes(summary, compiled.as_text())
        report["memory"] = {"argument_bytes": m.argument_size_in_bytes,
                            "output_bytes": m.output_size_in_bytes,
                            "alias_bytes": m.alias_size_in_bytes,
                            "temp_bytes": m.temp_size_in_bytes}
    shutil.rmtree(copy, ignore_errors=True)
    text = json.dumps(report)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

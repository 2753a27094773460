#!/usr/bin/env python3
"""Trim a recorded ``.xplane.pb`` to a small XSpace text proto for the tests.

    python3 bench/tests/make_trace_fixture.py <in.xplane.pb> <out.textproto> \
        [--ms 30] [--devices 0]

Keeps the host plane's ``bench.`` spans and each kept device plane's
``XLA Ops`` and ``XLA Modules`` events (with their string stats) that
start in the first ``--ms`` milliseconds of the ``bench.window`` span,
and cuts that span to the same length.  ``ProfileData.from_text_proto``
reads the result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce  # noqa: E402


def _q(s: str) -> str:
    return json.dumps(s)


class _Plane:
    def __init__(self, pid: int, name: str):
        self.pid, self.name = pid, name
        self.lines, self.events, self.stats = [], {}, {}

    def event_id(self, name: str) -> int:
        return self.events.setdefault(name, len(self.events) + 1)

    def stat_id(self, name: str) -> int:
        return self.stats.setdefault(name, len(self.stats) + 1)

    def add_line(self, lid: int, name: str, events):
        """events: (name, start_ns, dur_ns, {stat: str})."""
        if not events:
            return
        t0 = min(e[1] for e in events)
        out = [f"  lines {{ id: {lid} name: {_q(name)} timestamp_ns: {int(t0)}"]
        for n, s, d, st in events:
            stats = "".join(f" stats {{ metadata_id: {self.stat_id(k)} str_value: {_q(v)} }}"
                            for k, v in sorted(st.items()))
            out.append(f"    events {{ metadata_id: {self.event_id(n)} "
                       f"offset_ps: {int(round((s - t0) * 1000))} "
                       f"duration_ps: {int(round(d * 1000))}{stats} }}")
        out.append("  }")
        self.lines.append("\n".join(out))

    def text(self) -> str:
        out = [f"planes {{ id: {self.pid} name: {_q(self.name)}"]
        out += self.lines
        for n, i in self.events.items():
            out.append(f"  event_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} }} }}")
        for n, i in self.stats.items():
            out.append(f"  stat_metadata {{ key: {i} value {{ id: {i} name: {_q(n)} }} }}")
        out.append("}")
        return "\n".join(out)


def trim(profile, ms: float, devices) -> str:
    planes, w0 = [], None
    for plane in profile.planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        w0 = ev.start_ns
    if w0 is None:
        raise SystemExit("no bench.window span in the trace")
    w1 = w0 + ms * 1e6
    keep = lambda s: w0 <= s < w1
    for plane in profile.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if plane.name == trace_reduce.HOST_PLANE:
            p = _Plane(len(planes) + 1, plane.name)
            for k, line in enumerate(plane.lines):
                evs = []
                for ev in line.events:
                    if ev.name == trace_reduce.WINDOW_SPAN:
                        evs.append((ev.name, w0, w1 - w0, {}))
                    elif ev.name.startswith(trace_reduce.SPAN_PREFIX) and keep(ev.start_ns):
                        evs.append((ev.name, ev.start_ns, ev.duration_ns, {}))
                p.add_line(k + 1, line.name, evs)
            planes.append(p)
        elif m and int(m.group(1)) in devices:
            p = _Plane(len(planes) + 1, plane.name)
            for k, line in enumerate(plane.lines):
                if line.name not in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
                    continue
                evs = [(ev.name, ev.start_ns, ev.duration_ns,
                        trace_reduce._strings(ev.stats) if line.name == trace_reduce.OPS_LINE
                        else {})
                       for ev in line.events if keep(ev.start_ns)]
                p.add_line(k + 1, line.name, evs)
            planes.append(p)
    return "\n".join(p.text() for p in planes) + "\n"


def main(argv=None) -> int:
    from jax.profiler import ProfileData

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--ms", type=float, default=30.0)
    ap.add_argument("--devices", type=int, nargs="*", default=[0])
    a = ap.parse_args(argv)
    text = trim(ProfileData.from_file(a.src), a.ms, set(a.devices))
    with open(a.dst, "w") as f:
        f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())

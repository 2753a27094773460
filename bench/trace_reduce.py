"""Reduce a profiler trace of one measured window to what the metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes, read with
``jax.profiler.ProfileData``.  On a TPU v5e each chip is a plane
``/device:TPU:<id>`` whose ``XLA Ops`` line holds one event per operation
run, named by the text of its HLO instruction (``%reverse.20 = f32[...]
reverse(...)``), with a control-flow op such as a ``while`` holding the
events of its body, and whose ``XLA Modules`` line holds one event per
program run; the host plane ``/host:CPU`` holds the benchmark's own spans
(``jax.profiler.TraceAnnotation``, names starting ``bench.``) on the same
clock.  The window is the host span ``bench.window``.

From these the reduction gives, per chip used and averaged over them:

* busy time: the union of the operation intervals inside the window
  (control-flow ops included: the program is running);
* idle gaps: the stretches of the window with no operation running, each
  labelled with the innermost ``bench.`` span the host was in at its
  middle (``bench.window`` where it was in none of the inner ones);
* time per operation, by instruction name and counting only operations
  that hold no other (a loop's own time is its body's);
* the program runs of the ``XLA Modules`` line.

Run ``python bench/trace_reduce.py <file.xplane.pb>`` to print a trace's
planes, lines and busiest operations.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

Interval = Tuple[float, float]  # (start_ns, end_ns)
INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")


def instruction(event_name: str) -> str:
    """``fusion.7`` from ``%fusion.7 = f32[...] fusion(...), ...``."""
    m = INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name


@dataclasses.dataclass
class Device:
    """One chip's operations and program runs inside the window."""

    ops: List[Tuple[str, float, float]]  # (name, start_ns, end_ns), clipped
    modules: List[Tuple[str, float, float]]
    busy: List[Interval]  # merged, clipped to the window


@dataclasses.dataclass
class Summary:
    window: Interval
    devices: List[Device]
    spans: List[Tuple[str, float, float]]  # host bench.* spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return _mean([sum(e - s for s, e in d.busy) for d in self.devices]) * 1e-9

    def idle_gaps(self, device: int = 0) -> List[Tuple[float, float, str]]:
        """(start_ns, end_ns, host span) of every idle stretch of one chip."""
        w0, w1 = self.window
        gaps, t = [], w0
        for s, e in self.devices[device].busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if w1 > t:
            gaps.append((t, w1))
        return [(s, e, self.host_span_at((s + e) / 2)) for s, e in gaps]

    def host_span_at(self, t: float) -> str:
        inner = [(s, n) for n, s, e in self.spans if s <= t <= e and n != WINDOW_SPAN]
        return max(inner)[1] if inner else WINDOW_SPAN

    def breakdown(self, top: int = 10) -> dict:
        """The busiest operations and the longest idle gaps, in seconds."""
        per_op: Dict[str, float] = {}
        for d in self.devices:
            for n, s, e in d.ops:
                per_op[n] = per_op.get(n, 0.0) + (e - s) * 1e-9 / len(self.devices)
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps(), key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[lab, (e - s) * 1e-9] for s, e, lab in gaps]}

def _mean(xs):
    return sum(xs) / len(xs) if xs else 0.0


def _merge(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _leaves(events):
    """The events that hold no other event of their line."""
    events = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    return [ev for ev, nxt in zip(events, events[1:] + [None])
            if nxt is None or nxt[1] >= ev[2]]


def reduce(profile, device_ids: Optional[List[int]] = None) -> Summary:
    """Summary of a ``ProfileData`` for the chips ``device_ids`` (all TPU
    planes when None)."""
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = windows[0]

    devices = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or (device_ids is not None and int(m.group(1)) not in device_ids):
            continue
        ops, modules = [], []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                if line.name == OPS_LINE:
                    ops.append((instruction(ev.name), s, e))
                else:
                    modules.append((ev.name, s, e))
        busy = _merge([(s, e) for _, s, e in ops], w0, w1)
        devices.append(Device(ops=_leaves(ops), modules=sorted(modules, key=lambda m: m[1]),
                              busy=busy))
    if not devices:
        raise ValueError("no TPU plane with operations in the trace")
    return Summary(window=(w0, w1), devices=devices, spans=spans)


def reduce_dir(trace_dir: str, device_ids: Optional[List[int]] = None) -> Summary:
    """Summary of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, found {files}")
    return reduce(ProfileData.from_file(files[0]), device_ids)


def describe(profile, events: int = 5) -> str:
    """Planes, lines, a few events of each with their stats, for a look by hand."""
    out = []
    for plane in profile.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:events]:
                out.append(f"    {ev.name!r} start {ev.start_ns} dur {ev.duration_ns} "
                           f"{dict(ev.stats)}")
    return "\n".join(out)


if __name__ == "__main__":
    from jax.profiler import ProfileData

    print(describe(ProfileData.from_file(sys.argv[1])))

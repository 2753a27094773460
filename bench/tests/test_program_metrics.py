"""The per-layer metrics that read the serving engine's own counters, and
the readers that were there before, on traces and counters that hold the
program's ``serve.`` spans and counters or lack them (as a program without
them gives)."""

import os

import pytest
from jax.profiler import ProfileData

import harness
import trace_reduce
from test_trace_reduce import HAND, metric

CELL = "deblur_sec7_1024.serve_stratified"
NEW = ("queue_wait_s.serve", "d2h_mb_per_request.serve")
OLD = ("idle_share.serve", "slot_fill.serve", "round_gap_ms.serve")

# the program's spans inside the benchmark's bench.step [35, 45] us, over
# the middle of the idle gap [20, 60]: a scheduling round with one
# admission and a harvest that fetched one finished lane
SERVE = {11: "serve.step", 12: "serve.admit", 13: "serve.round", 14: "serve.harvest",
         15: "serve.harvest.fetch"}
SERVE_EVENTS = ("events { metadata_id: 11 offset_ps: 36000000 duration_ps: 8000000 } "
                "events { metadata_id: 12 offset_ps: 36000000 duration_ps: 1000000 } "
                "events { metadata_id: 13 offset_ps: 37000000 duration_ps: 1000000 } "
                "events { metadata_id: 14 offset_ps: 38000000 duration_ps: 5000000 } "
                "events { metadata_id: 15 offset_ps: 39000000 duration_ps: 3000000 }")
METAS = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                  for i, n in SERVE.items())


def with_program_spans(text):
    """The hand trace with the program's spans on a second host line."""
    line = f'lines {{ id: 2 name: "python" timestamp_ns: 1000 {SERVE_EVENTS} }}'
    head, tail = text.split("  lines { id: 1 name: \"main\"", 1)
    host, rest = tail.split("\n}\n", 1)
    return f"{head}  lines {{ id: 1 name: \"main\"{host}\n  {line}\n  {METAS}\n}}\n{rest}"


COUNTERS = {"window_rounds": 2, "slots": 8, "round_iters": 32, "window_slot_iters": 256,
            "window_admitted": 4, "window_queue_wait_s": 3.0, "window_waiting_s": 1.5,
            "requests": 6, "window_harvested": 5, "window_d2h_bytes": 125_000_000}
WITHOUT = {k: v for k, v in COUNTERS.items()
           if k not in ("window_queue_wait_s", "window_waiting_s", "window_harvested",
                        "window_d2h_bytes")}


def reading(trace, counters):
    return harness.Reading(cell={}, config={}, traffic={}, counters=counters,
                           trace=trace, device_kind="TPU v5 lite")


def test_the_hand_trace_holds_the_program_spans():
    text = with_program_spans(HAND)
    names = {ev.name for p in ProfileData.from_text_proto(text).planes
             if p.name == trace_reduce.HOST_PLANE for line in p.lines for ev in line.events}
    assert set(SERVE.values()) <= names


def test_readers_there_before_read_the_same_with_the_program_spans():
    plain = trace_reduce.reduce(ProfileData.from_text_proto(HAND))
    spanned = trace_reduce.reduce(ProfileData.from_text_proto(with_program_spans(HAND)))
    for name in OLD:
        a = metric(name).read(reading(plain, WITHOUT))
        b = metric(name).read(reading(spanned, COUNTERS))
        assert a is not None and a == b, name
    assert plain.idle_gaps() == spanned.idle_gaps()
    assert plain.breakdown() == spanned.breakdown()


def test_new_readers_against_known_answers():
    r = reading(None, COUNTERS)
    # (3.0 s admitted + 1.5 s still queued) over 6 arrivals
    assert metric("queue_wait_s.serve").read(r) == pytest.approx(0.75)
    assert metric("d2h_mb_per_request.serve").read(r) == pytest.approx(25.0)


@pytest.mark.parametrize("counters", [
    WITHOUT, {}, {**COUNTERS, "requests": 0, "window_harvested": 0},
    {k: v for k, v in COUNTERS.items() if k not in ("window_waiting_s", "window_d2h_bytes")}])
def test_new_readers_stay_silent_without_their_counters(counters):
    for name in NEW:
        assert metric(name).read(reading(None, counters)) is None


def test_new_metrics_are_listed_for_the_served_cell():
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    specs = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        m = specs[name]
        assert m["workloads"] == [CELL] and m["moves"] == "p90_latency_s"
        assert m["source"] == "program_counter"
        assert os.path.isfile(os.path.join(harness.BENCH, "metrics", name + ".py"))

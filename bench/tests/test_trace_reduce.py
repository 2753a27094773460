"""The trace reduction and the readers built on it: on a hand-made trace
with known answers, and on 250 ms of a real TPU v5e trace of a 200-iteration
CPADMM solve of one 2^24 signal (trimmed by ``make_trace_fixture.py``)."""

import json
import os

import pytest
from jax.profiler import ProfileData

import harness
import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
US = 1_000_000  # picoseconds in a microsecond


def _event(meta, start_us, dur_us):
    return f"events {{ metadata_id: {meta} offset_ps: {start_us * US} duration_ps: {dur_us * US} }}"


def _names(names):
    return "\n".join(f"event_metadata {{ key: {i} value {{ id: {i} name: {json.dumps(n)} }} }}"
                     for i, n in names.items())


HOST = {1: "bench.window", 2: "bench.call", 3: "bench.step", 4: "bench.wait"}
OPS = {1: "%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %tuple.1), condition=%c",
       2: "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %reverse.2), kind=kLoop",
       3: "%reverse.2 = f32[8]{0} reverse(f32[8]{0} %p), dimensions={0}",
       4: "%cpadmm_tail_pallas.3 = (f32[8]{0}) custom-call(f32[8]{0} %fusion.7)",
       5: "%copy.1 = f32[8]{0} copy(f32[8]{0} %p)",
       6: "jit_round_fn(123)"}
HAND = f"""
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "main" timestamp_ns: 1000
    {_event(1, 0, 100)} {_event(2, 10, 20)} {_event(3, 35, 10)} {_event(4, 70, 30)}
  }}
  {_names(HOST)}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 1000
    {_event(1, -3, 23)} {_event(2, -3, 8)} {_event(3, 5, 4)} {_event(4, 12, 8)}
    {_event(5, 60, 5)} {_event(4, 95, 10)}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 1000
    {_event(6, -3, 23)} {_event(6, 60, 5)}
  }}
  {_names(OPS)}
}}
"""
def hand():
    return trace_reduce.reduce(ProfileData.from_text_proto(HAND))


def metric(name):
    return harness.load_module(os.path.join(harness.BENCH, "metrics", name + ".py"),
                               "t_" + name.replace(".", "_"))


def test_busy_union_is_clipped_to_the_window():
    s = hand()
    assert s.window_s == pytest.approx(100e-6)
    # the while loop [0, 20] (started before the window, its body's gap
    # included) + [60, 65] + [95, 100] (cut at the window's end)
    assert s.busy_s == pytest.approx(30e-6)


def test_idle_gaps_carry_the_host_span_they_fall_in():
    gaps = [((e - s) / 1e3, lab) for s, e, lab in hand().idle_gaps()]
    assert gaps == [(40.0, "bench.step"), (30.0, "bench.wait")]


def test_op_time_counts_leaves_by_instruction():
    s = hand()
    names = [n for n, _, _ in s.devices[0].ops]
    assert "while.1" not in names  # a loop's own time is its body's
    assert names.count("cpadmm_tail_pallas.3") == 2


def test_breakdown_lists_ops_and_gaps_in_seconds():
    b = hand().breakdown()
    assert b["device_ops"][0] == ["cpadmm_tail_pallas.3", pytest.approx(13e-6)]
    assert b["idle_gaps"][0] == ["bench.step", pytest.approx(40e-6)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_readers_on_the_hand_trace():
    r = harness.Reading(cell={}, config={}, traffic={},
                        counters={"window_rounds": 2, "slots": 8, "round_iters": 32,
                                  "window_slot_iters": 256},
                        trace=hand(), device_kind="TPU v5 lite")
    assert metric("idle_share.serve").read(r) == pytest.approx(70.0)
    assert metric("slot_fill.serve").read(r) == pytest.approx(50.0)
    # between the two round programs the device idles 40 us, none of it
    # in an arrival wait
    assert metric("round_gap_ms.serve").read(r) == pytest.approx(0.040)


def test_readers_stay_silent_without_a_trace():
    r = harness.Reading(cell={}, config={}, traffic={}, counters={}, trace=None,
                        device_kind="TPU v5 lite")
    for name in ("idle_share.serve", "slot_fill.serve", "round_gap_ms.serve"):
        assert metric(name).read(r) is None


def real():
    with open(os.path.join(DATA, "v5e_cs_sec6_2p24.textproto")) as f:
        return trace_reduce.reduce(ProfileData.from_text_proto(f.read()), [0])


def test_real_v5e_trace():
    s = real()
    assert s.window_s == pytest.approx(0.25)
    assert s.busy_s == pytest.approx(0.241770839)
    leaves = sorted((a, b) for d in s.devices for _, a, b in d.ops)
    assert all(b1 <= a2 for (_, b1), (a2, _) in zip(leaves, leaves[1:]))  # disjoint
    # the call's set-up scatters, then the first iteration: one tail and
    # two spectral updates (the second starting the next iteration), with
    # the transforms' reverses the slowest ops
    names = [n.split(".")[0] for n, _, _ in s.devices[0].ops]
    assert names.count("cpadmm_tail_pallas") == 1
    assert names.count("cpadmm_spectral_update") == 2
    top = [name for name, _ in s.breakdown()["device_ops"]]
    assert sum(n.startswith("reverse.") for n in top) == 4
    assert all(lab == "bench.call" for _, _, lab in s.idle_gaps())

"""The analysis of a traced run by the program's own spans and scopes
(``bench/span_report.py``), on the hand-made trace with the program's
``serve.`` spans and a compiled text written for it."""

import pytest
from jax.profiler import ProfileData

import span_report
import trace_reduce
from test_program_metrics import with_program_spans
from test_trace_reduce import HAND

TEXT = """HloModule jit_round_fn, entry_computation_layout={()->f32[8]{0}}
ENTRY %main {
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %reverse.2), kind=kLoop, metadata={op_name="jit(round_fn)/while/body/cpadmm.xupdate/transform/jit(fft)" source_file="x.py"}
  %reverse.2 = f32[8]{0} reverse(f32[8]{0} %p), dimensions={0}
  %cpadmm_tail_pallas.3 = (f32[8]{0}) custom-call(f32[8]{0} %fusion.7), metadata={op_name="jit(round_fn)/while/body/cpadmm.tail/pallas_call"}
  ROOT %copy.1 = f32[8]{0} copy(f32[8]{0} %p), metadata={op_name="jit(round_fn)/while/body/until.test/jit(norm)/reduce_sum"}
}
"""


def _profile():
    return ProfileData.from_text_proto(with_program_spans(HAND))


def test_program_spans_are_read_with_their_arguments_cut():
    spans = span_report.program_spans(_profile())
    assert [n for n, _, _ in spans] == ["serve.step", "serve.admit", "serve.round",
                                        "serve.harvest", "serve.harvest.fetch"]
    assert all(n.startswith("serve.") and e > s for n, s, e in spans)


def test_idle_gaps_take_the_innermost_span_of_either_kind():
    prof = _profile()
    summary = trace_reduce.reduce(prof)
    got = span_report.idle(summary, span_report.program_spans(prof))
    # the gap [20, 60] us has its middle in serve.harvest.fetch [39, 42],
    # inside bench.step; the gap [65, 95] in bench.wait
    assert [[lab, round(d * 1e6, 6)] for lab, d in got["longest"]] == [
        ["serve.harvest.fetch", 40.0], ["bench.wait", 30.0]]
    assert got["idle_s"] == pytest.approx(70e-6)
    assert got["by_label"]["serve.harvest.fetch"]["count"] == 1
    # the benchmark's own labels are what they were
    assert [lab for _, _, lab in summary.idle_gaps()] == ["bench.step", "bench.wait"]


def test_span_durations_in_the_window():
    prof = _profile()
    ms = span_report.span_ms(trace_reduce.reduce(prof), span_report.program_spans(prof))
    assert ms["serve.step"] == {"count": 1, "mean": pytest.approx(0.008),
                                "max": pytest.approx(0.008), "sum": pytest.approx(0.008)}
    assert ms["serve.harvest.fetch"]["mean"] == pytest.approx(0.003)


def test_instructions_keep_their_op_names():
    instr = span_report.instructions(TEXT)
    assert instr["reverse.2"][0] is None
    assert instr["copy.1"][0].endswith("until.test/jit(norm)/reduce_sum")
    assert set(instr) == {"fusion.7", "reverse.2", "cpadmm_tail_pallas.3", "copy.1"}


def test_round_op_time_splits_by_scope():
    got = span_report.scopes(trace_reduce.reduce(_profile()), TEXT)
    # leaves inside the round programs [0, 20] and [60, 65]: fusion.7 5 us
    # (transform), reverse.2 4 us (no op_name), the tail 8 us, copy.1 5 us
    # (until.test); the tail at [95, 100] runs outside any round program
    assert got["round_op_s"] == pytest.approx(22e-6)
    assert got["named_share"] == pytest.approx(100 * 18 / 22)
    share = got["scope_share"]
    assert share["transform"] == pytest.approx(100 * 5 / 22)
    assert share["cpadmm.xupdate"] == pytest.approx(100 * 5 / 22)
    assert share["cpadmm.tail"] == pytest.approx(100 * 8 / 22)
    assert share["until.test"] == pytest.approx(100 * 5 / 22)
    assert share["until.freeze"] == 0
    (bare,) = got["unattributed"]
    assert bare["op"] == "reverse.2" and bare["in_text"]
    assert bare["hlo"].startswith("%reverse.2 = f32[8]{0} reverse(")


def test_no_round_program_reads_nothing():
    summary = trace_reduce.reduce(_profile())
    summary.devices[0].modules = []
    assert span_report.scopes(summary, TEXT) == {"round_op_s": 0.0}


def test_keeping_holds_the_engine_and_restores_what_it_hooks(tmp_path):
    import time

    import jax
    from repro.serve import RecoveryServer
    from test_rehearsal import CELLS, SECONDS

    import harness

    cell = "deblur_sec7_1024.serve_stratified"
    reduce_dir, warmup = trace_reduce.reduce_dir, RecoveryServer.warmup
    engines = []
    with span_report.keeping(str(tmp_path / "trace"), engines):
        r = harness.run_cell(cell, 20231117, SECONDS, False, t_start=time.perf_counter(),
                             devices=jax.devices(), overrides=CELLS[cell])
    assert r["correct"], r["checks"]
    assert (trace_reduce.reduce_dir, RecoveryServer.warmup) == (reduce_dir, warmup)
    (eng,) = engines
    text = eng.lower_round().compile().as_text()
    scoped = {n for n, _ in span_report.instructions(text).values() if n}
    for scope in ("transform", "cpadmm.tail", "until.test"):
        assert any(f"/{scope}/" in n for n in scoped), scope

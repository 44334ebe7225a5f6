"""The trace reducer on hand-made cases and on a small recorded trace."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def ev(name, start, dur, scope=""):
    return [name, float(start), float(dur), scope]


def test_union_merges_overlaps_and_nesting():
    assert tr.union([(0, 10), (5, 12), (20, 30), (22, 25)]) == [
        (0, 12), (20, 30)]
    assert tr.total(tr.union([(0, 10), (10, 15)])) == 15


def test_busy_is_a_union_not_a_sum():
    # a while op spanning its body's ops must not count twice
    events = [ev("while.1", 0, 100), ev("fusion.1", 0, 40),
              ev("fusion.2", 50, 50), ev("copy.3", 200, 100)]
    assert tr.busy_seconds(events) == pytest.approx(200e-9)
    assert tr.busy_seconds(events, 50, 250) == pytest.approx(100e-9)


def test_sum_by_name_counts_leaves_only_and_names_the_result_shape():
    events = [ev("while.1", 0, 100, "%while.1 = (s32[]) while(...)"),
              ev("fusion.1", 0, 40, "%fusion.1 = bf16[4,8]{1,0} fusion(...)"),
              ev("fusion.1", 50, 50, "%fusion.1 = bf16[4,8]{1,0} fusion(...)"),
              ev("copy.3", 200, 100, "%copy.3 = (f32[2]{0}, u32[]) copy(...)")]
    sums = dict(tr.sum_by_name(events))
    assert sums == {"copy.3 f32[2]": pytest.approx(100e-9),
                    "fusion.1 bf16[4,8]": pytest.approx(90e-9)}
    assert tr.op_name("%flash_attention.22 = (bf16[64,1024,64]{2,1,0}) "
                      "custom-call(%bitcast.672)") == "flash_attention.22"
    assert tr.op_name("bench:train_step") == "bench:train_step"


def test_pattern_matches_name_or_scope():
    events = [ev("custom-call.7", 0, 10, "jit(step)/gpt_attention/"
                 "flash_attention/pallas_call"),
              ev("fusion.9", 10, 10, "jit(step)/gpt_mlp/dot_general")]
    assert tr.intervals(events, "flash_attention") == [(0.0, 10.0)]
    assert tr.intervals(events, "fusion", exclude="gpt_mlp") == []


def test_idle_gaps_and_attribution_to_host_spans():
    events = [ev("a", 0, 10), ev("b", 30, 10), ev("c", 100, 10)]
    gaps = tr.idle_gaps(events, 0, 110)
    assert gaps == [(10, 30), (40, 100)]
    spans = [("bench:step", 0, 60), ("bench:wait", 35, 50),
             ("bench:batch", 60, 90)]
    by = dict(tr.gaps_by_host_span(gaps, spans))
    # gap (10,30) lies in step; gap (40,100): wait covers 40-50 (innermost,
    # it starts later than step), step 50-60, batch 60-90, nothing 90-100
    assert by["bench:step"] == pytest.approx(30e-9)
    assert by["bench:wait"] == pytest.approx(10e-9)
    assert by["bench:batch"] == pytest.approx(30e-9)
    assert by["(no benchmark span)"] == pytest.approx(10e-9)


def test_exposed_collective_time_two_tracks():
    # a collective of 100 ns; compute covers 30 ns of it (20-50), under a
    # while op that spans everything and must not hide the exposure
    events = [ev("while.1", 0, 300),
              ev("all-reduce.1", 10, 100),
              ev("fusion.1", 20, 30),
              ev("fusion.2", 150, 50),
              ev("reduce-scatter.2", 160, 20)]     # fully hidden
    pattern = "all-reduce|reduce-scatter"
    assert tr.exposed_seconds(events, pattern) == pytest.approx(70e-9)


def test_device_ops_picks_tpu_planes_only():
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [ev("fusion.1", 0, 5)]},
            {"name": "Steps", "events": [ev("1", 0, 50)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [ev("fusion.1", 1, 5)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [ev("bench:train_step", 0, 9),
                                          ev("other", 0, 9)]}]}]}
    ops = tr.device_ops(trace)
    assert sorted(ops) == [0, 1] and len(ops[0]) == 1
    assert tr.host_spans(trace, "bench:") == [("bench:train_step", 0.0, 9.0)]
    assert tr.span_of(trace) == (0.0, 6.0)


def test_program_runs_counts_the_steps_the_device_ran():
    # two chips: the fewest runs and the longest span; another program's
    # runs and a chip that never ran it do not count
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": [
            ev("jit_train_step(1)", 0, 40), ev("jit_train_step(1)", 50, 40),
            ev("jit_norms(2)", 95, 5)]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Modules", "events": [
            ev("jit_train_step(1)", 10, 40), ev("jit_train_step(1)", 60, 40),
            ev("jit_train_step(1)", 110, 40)]}]},
        {"name": "/device:TPU:2", "lines": [{"name": "XLA Modules",
                                             "events": []}]}]}
    runs, seconds = tr.program_runs(trace, "^jit_train_step")
    assert runs == 2 and seconds == pytest.approx(140e-9)
    assert tr.program_runs(trace, "^jit_decode_step") is None


RECORDED = os.path.join(HERE, "data", "trace_small.json")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_recorded_chip_trace_reduces():
    with open(RECORDED) as f:
        rec = json.load(f)
    trace, expect = rec["trace"], rec["expect"]
    ops = tr.device_ops(trace)
    assert sorted(ops) == expect["chips"]
    lo, hi = tr.span_of(trace)
    busy = tr.busy_seconds(ops[0], lo, hi)
    assert 0 < busy <= (hi - lo) * 1e-9
    assert busy == pytest.approx(expect["busy_s"], rel=1e-9)
    # a sum over names can exceed the union only through nesting, which
    # sum_by_name leaves out
    assert sum(v for _, v in tr.sum_by_name(ops[0], top=10 ** 6)) \
        <= busy * (1 + 1e-9)
    for pattern, seconds in expect["pattern_seconds"].items():
        got = tr.total(tr.intervals(ops[0], pattern)) * 1e-9
        assert got == pytest.approx(seconds, rel=1e-9)
    assert tr.host_spans(trace, "bench:")
    steps, seconds = tr.program_runs(trace, "^jit_train_step")
    assert steps == 7 and seconds == pytest.approx(7 * 0.5755, rel=1e-3)

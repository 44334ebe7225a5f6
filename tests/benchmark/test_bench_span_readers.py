"""The readers of the program's own spans, held to exact values on
hand-made traces; the manifest's new entries against their files."""

import json
import os

import pytest

from benchmark import run as harness
from benchmark.readers import idle_under_span, program_span, shape_share

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
SPAN_METRICS = ["prefill_roundtrip_ms.serve", "decode_host_ms.serve",
                "scheduler_self_ms.serve", "dispatch_gap_ms.serve",
                "fetch_gap_ms.serve", "pool_rewrite_share.serve"]
MS = 1e6          # nanoseconds
POOL = "bf16[2,9,8,64]"       # (layers, blocks, block_size, hidden): PR 27's
LAYER = "bf16[1,9,8,64]"
CONFIG = {"n_layer": 2, "n_head": 4, "n_embd": 64}
CELL = {"engine": {"num_blocks": 9, "block_size": 8}}


def ev(name, start_ms, dur_ms, text=""):
    return [name, start_ms * MS, dur_ms * MS, text]


def op(name, start_ms, dur_ms, result):
    return ev(name, start_ms, dur_ms, f"%{name} = {result}{{4,3,2,1,0}} op()")


def make_trace(host, ops, modules):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": host}]},
    ]}


def facts_of(trace, **more):
    from benchmark import trace_reduce
    return dict(trace=trace, device_ops=trace_reduce.device_ops(trace),
                config=CONFIG, cell=CELL, chips=1, **more)


# Two scheduler steps. Step one (0-100 ms) admits a request (prefill 2-22)
# and decodes (30-98, waiting 40-97); step two (100-200) only decodes
# (102-196, waiting 110-195) and retires a slot (197-199).
HOST = [
    ev("apex:sched.step", 0, 100),
    ev("apex:sched.admit", 1, 23),
    ev("apex:engine.prefill", 2, 20),
    ev("apex:prefill.plan", 2, 3),
    ev("apex:prefill.dispatch", 5, 1),
    ev("apex:prefill.wait", 6, 16),
    ev("apex:engine.decode", 30, 68),
    ev("apex:decode.plan", 30, 6),
    ev("apex:decode.dispatch", 36, 2),
    ev("apex:decode.advance", 38, 2),
    ev("apex:decode.wait", 40, 57),
    ev("apex:sched.step", 100, 100),
    ev("apex:engine.decode", 102, 94),
    ev("apex:decode.plan", 102, 5),
    ev("apex:decode.dispatch", 107, 3),
    ev("apex:decode.wait", 110, 85),
    ev("apex:sched.harvest", 196, 3.5),
    ev("apex:engine.release", 197, 2),
    ev("bench:scheduler.step", 0, 100),
    ev("bench:scheduler.step", 100, 101),
]
# The device: prefill program 6-20; decode program 37-95 and 108-194; the
# release program 199-200 ends the window. Idle: 20-37, 95-108, 194-199.
OPS = [
    op("fusion.1", 6, 14, "bf16[1,512,64]"),
    op("while.2", 37, 58, "(s32[])"),            # spans its body: no leaf
    op("copy.47", 37, 20, POOL),
    op("copy.71", 57, 8, LAYER),
    op("paged_decode_attention.3", 65, 30, "bf16[2,4,1,16]"),
    op("copy.47", 108, 30, POOL),
    op("fusion.9", 138, 56, "f32[2,97]"),
    op("fusion.12", 199, 1, POOL),               # not in a decode run
]
MODULES = [ev("jit_prefill_step(1)", 6, 14), ev("jit_decode_step(2)", 37, 58),
           ev("jit_decode_step(2)", 108, 86), ev("jit_release_step(3)", 199, 1)]
TRACE = make_trace(HOST, OPS, MODULES)


def spec(metric):
    return harness.load_json(BENCH, "metrics", metric + ".json")


def test_span_median_and_none_where_the_span_does_not_occur():
    facts = facts_of(TRACE)
    assert program_span.read(facts, {"span": "apex:engine.prefill",
                                     "stat": "median"}) \
        == pytest.approx(20.0)
    assert program_span.read(facts, {"span": "apex:engine.decode",
                                     "stat": "median"}) \
        == pytest.approx((68 + 94) / 2)
    assert program_span.read(facts, {"span": "apex:engine.decode",
                                     "stat": "mean"}) == pytest.approx(81.0)
    assert program_span.read(facts, {"span": "apex:engine.verify",
                                     "stat": "median"}) is None
    # a prefix of a name is not the name
    assert program_span.read(facts, {"span": "apex:engine",
                                     "stat": "median"}) is None


def test_self_time_leaves_out_the_named_children_inside():
    facts = facts_of(TRACE)
    # decode less its wait: 68 - 57 = 11 and 94 - 85 = 9
    from benchmark import trace_reduce
    assert program_span.self_times_ms(
        trace_reduce.host_spans(TRACE, "apex:"), "apex:engine.decode",
        ["apex:decode.wait"]) == pytest.approx([11.0, 9.0])
    assert program_span.read(facts, spec("decode_host_ms.serve")["params"]) \
        == pytest.approx(10.0)
    # a step less the engine's calls: 100 - 20 - 68 = 12; 100 - 94 - 2 = 4
    assert program_span.read(
        facts, spec("scheduler_self_ms.serve")["params"]) \
        == pytest.approx(8.0)
    assert program_span.read(
        facts, spec("prefill_roundtrip_ms.serve")["params"]) \
        == pytest.approx(20.0)


def test_self_time_counts_an_overlap_once_and_clips_at_the_span():
    host = [ev("apex:sched.step", 0, 10), ev("apex:engine.decode", 2, 4),
            ev("apex:engine.prefill", 1, 6),      # holds the decode
            ev("apex:engine.release", 9, 5)]      # runs past the step's end
    params = dict(spec("scheduler_self_ms.serve")["params"], stat="mean")
    assert program_span.read(facts_of(make_trace(host, [], [])), params) \
        == pytest.approx(10 - 6 - 1)


def test_idle_gaps_go_to_the_innermost_program_span():
    by = idle_under_span.gaps_by_span(TRACE)
    ms = {k: v * 1e3 for k, v in by.items()}
    # the window is the device's: 6-200 ms. Idle: 20-37, 95-108, 194-199
    assert ms == {
        # 20-37: prefill.wait to 22 (engine.prefill ends with it), admit to
        # 24, step to 30, decode.plan to 36, decode.dispatch 36-37
        "apex:prefill.wait": pytest.approx(2),
        "apex:sched.admit": pytest.approx(2),
        "apex:sched.step": pytest.approx(6 + 2 + 2),
        "apex:decode.plan": pytest.approx(6 + 5),
        "apex:decode.dispatch": pytest.approx(1 + 1),
        # 95-108: wait to 97, engine.decode to 98, step one to 100, step
        # two to 102, plan (one start with its engine.decode: the shorter
        # is the inner) to 107, dispatch 107-108
        "apex:decode.wait": pytest.approx(2 + 1),
        "apex:engine.decode": pytest.approx(1 + 1),
        # 194-199: wait to 195, engine.decode to 196, harvest to 197,
        # release 197-199
        "apex:sched.harvest": pytest.approx(1),
        "apex:engine.release": pytest.approx(2),
    }


def test_idle_under_span_per_decode_run():
    facts = facts_of(TRACE)
    dispatch = spec("dispatch_gap_ms.serve")["params"]
    fetch = spec("fetch_gap_ms.serve")["params"]
    by = idle_under_span.gaps_by_span(TRACE)
    assert idle_under_span.read(facts, fetch) == pytest.approx((3 + 2) / 2)
    assert idle_under_span.read(facts, dispatch) \
        == pytest.approx((sum(by.values()) * 1e3 - 5) / 2)
    # the two metrics and what no apex span covers are the whole idle time
    from benchmark import trace_reduce
    idle = trace_reduce.total(trace_reduce.idle_gaps(
        OPS, *trace_reduce.span_of(TRACE))) * 1e-9
    assert sum(by.values()) == pytest.approx(idle)
    assert idle == pytest.approx((17 + 13 + 5) * 1e-3)


def test_idle_under_span_zero_and_none():
    # the device never waits under a wait span: 0.0, not None
    host = [ev("apex:sched.step", 0, 10), ev("apex:decode.wait", 2, 6)]
    ops = [op("fusion.1", 1, 8, "f32[2]")]
    modules = [ev("jit_decode_step(2)", 1, 8)]
    facts = facts_of(make_trace(host, ops, modules))
    assert idle_under_span.read(
        facts, {"spans": ["apex:decode.wait"], "per": "^jit_decode_step"}) \
        == 0.0
    # another program: None; a program from before the spans: None
    assert idle_under_span.read(
        facts, {"spans": ["apex:decode.wait"], "per": "^jit_train_step"}) \
        is None
    bare = facts_of(make_trace([ev("bench:scheduler.step", 0, 10)], ops,
                               modules))
    assert idle_under_span.read(
        bare, {"spans": ["apex:decode.wait"], "per": "^jit_decode_step"}) \
        is None
    assert idle_under_span.gaps_by_span(bare["trace"]) is None


def test_the_gap_metrics_split_every_serving_span_between_them():
    from apex_tpu.observability.trace import SPANS, SPAN_PREFIX
    dispatch = set(spec("dispatch_gap_ms.serve")["params"]["spans"])
    fetch = set(spec("fetch_gap_ms.serve")["params"]["spans"])
    assert not dispatch & fetch
    # construction and the speculative step are not this cell's
    owed = {SPAN_PREFIX + n for n in SPANS
            if not n.startswith(("compile.", "verify."))
            and n not in ("engine.build", "engine.lint", "engine.verify")}
    assert dispatch | fetch == owed
    assert fetch == {SPAN_PREFIX + "decode.wait", SPAN_PREFIX + "prefill.wait"}


def test_shape_share_of_the_decode_runs_leaf_time():
    facts = facts_of(TRACE)
    params = spec("pool_rewrite_share.serve")["params"]
    # leaves inside decode runs: 20 + 8 + 30 + 30 + 56 = 144 ms, of which
    # the pool's shape 50 and one layer's 8; fusion.12 has the shape but
    # ran in the release program, while.2 is no leaf
    assert shape_share.read(facts, params) \
        == pytest.approx(100.0 * 58 / 144)
    assert shape_share.read(
        facts, dict(params, shapes=[params["shapes"][1]])) \
        == pytest.approx(100.0 * 8 / 144)


def test_shape_share_zero_and_none():
    facts = facts_of(TRACE)
    assert shape_share.read(facts, {"module": "^jit_decode_step",
                                    "shapes": [[7, 7]]}) == 0.0
    assert shape_share.read(facts, {"module": "^jit_train_step",
                                    "shapes": [[7, 7]]}) is None


def test_dimensions_come_from_the_configuration_and_the_cell():
    dims = spec("pool_rewrite_share.serve")["params"]["shapes"]
    assert [[shape_share.dimension(d, CONFIG, CELL) for d in shape]
            for shape in dims] == [[2, 9, 8, 64], [1, 9, 8, 64]]
    # a quotient of two configuration keys: the head size
    assert shape_share.dimension("n_embd/n_head", CONFIG, CELL) == 16
    large = harness.load_json(BENCH, "configs", "gpt2-large.json")
    cell = harness.load_json(BENCH, "workloads", "gpt2-large.chat-r80.json")
    assert [shape_share.dimension(d, large, cell) for d in dims[0]] \
        == [36, 257, 128, 1280]
    assert shape_share.result_dims(
        "%copy.47 = bf16[36,257,128,1280]{3,2,1,0} copy(%p)") \
        == [36, 257, 128, 1280]
    assert shape_share.result_dims(
        "%f = (f32[2]{0}, u32[]) fusion()") == [2]
    assert shape_share.result_dims("%w = () while()") is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_new_entries_match_their_files_and_list_the_serve_cell(metric):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == ["gpt2-large.chat-r80"]
    layers = {m["layer"] for m in MANIFEST["per_layer"]
              if m["name"] not in SPAN_METRICS}
    assert entry["layer"] in layers          # a layer the manifest has
    data = spec(metric)
    assert data["name"] == metric
    assert data["reader"] in ("program_span", "idle_under_span",
                              "shape_share")
    # appended: nothing that was there moved
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-len(SPAN_METRICS):] == SPAN_METRICS


def test_the_readers_give_nothing_for_a_program_without_spans():
    """What the parent commit's traced run gives: device ops, the
    benchmark's own spans, no ``apex:`` span."""
    host = [h for h in HOST if h[0].startswith("bench:")]
    facts = facts_of(make_trace(host, OPS, MODULES))
    for metric in SPAN_METRICS[:5]:
        data = spec(metric)
        reader = __import__("benchmark.readers." + data["reader"],
                            fromlist=["read"])
        assert reader.read(facts, data["params"]) is None, metric
    # the pool's copies are the compiler's: read with or without spans
    assert shape_share.read(
        facts, spec("pool_rewrite_share.serve")["params"]) is not None

"""The program's own spans (``apex_tpu.observability.trace.span``): a tiny
paged engine under ``SlotScheduler`` inside a profiler session, the
``.xplane.pb`` read back with the benchmark's reducer; the same run under
``span_recording()``; and the table of names against the code and the
documents that copy it."""

import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.observability import trace
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.serving import (PagedServingEngine, Request, ServingEngine,
                              SlotScheduler)
from apex_tpu.utils.timers import Timer, profile_trace

from _program_text import program_text
from benchmark import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PREFIX = trace.SPAN_PREFIX
BUILD = {"engine.build", "compile.image", "compile.prefill",
         "compile.decode", "compile.verify", "compile.release",
         "engine.lint"}
VERIFY = {"engine.verify", "verify.plan", "verify.dispatch", "verify.wait",
          "verify.advance"}
# what three requests on two paged slots produce once the engine is built
SERVED = set(trace.SPANS) - BUILD - VERIFY


@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    compute_dtype=jnp.float32)
    model = GPTModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def paged_engine(model_params):
    model, params = model_params
    return PagedServingEngine(model, params, max_seqs=2, max_len=24,
                              prefill_len=8, num_blocks=16, block_size=4,
                              cache_dtype=jnp.float32)


def serve(engine):
    """Three requests on two slots: the third waits for a slot, and its
    prompt shares the first's first block, so it is admitted through the
    prefix-hit path (decode steps inside its prefill). One deadline, so
    the expiry walk runs. Returns the scheduler's counters."""
    reg = MetricsRegistry()
    sched = SlotScheduler(engine, registry=reg)
    for prompt, new in (([1, 2, 3, 4, 5, 6], 3), ([7, 8, 9], 4),
                        ([1, 2, 3, 4, 9], 2)):
        sched.submit(Request(prompt=prompt, max_new_tokens=new,
                             deadline_ms=60_000.0))
    while sched.pending:
        sched.step()
    return dict(reg.snapshot())


def host_events(path):
    """``[(name, start_ns, end_ns, stats)]`` of the ``apex:`` events."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


@pytest.fixture(scope="module")
def profiled(model_params, tmp_path_factory):
    """One served run inside a profiler session: the reduced trace, the
    events with their stats, the scheduler's counters."""
    engine = paged_engine(model_params)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    assert not trace.spans_enabled()
    with profile_trace(log_dir, host_tracer_level=1):
        counters = serve(engine)
    assert trace.drain_spans() == []       # recording was off throughout
    path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return (trace_reduce.load_xplane(path), host_events(path), counters)


@pytest.fixture(scope="module")
def recorded(model_params):
    """The same run under ``span_recording()``, engine construction
    included: the drained spans and the counters."""
    with trace.span_recording():
        counters = serve(paged_engine(model_params))
        spans = trace.drain_spans()
    return spans, counters


def inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_every_span_the_traffic_can_produce_is_in_the_profile(profiled):
    reduced, _, _ = profiled
    names = {n[len(PREFIX):] for n, _, _ in
             trace_reduce.host_spans(reduced, PREFIX)}
    assert names == SERVED


@pytest.mark.parametrize("inner,outer", [
    ("decode.wait", "engine.decode"), ("decode.plan", "engine.decode"),
    ("decode.dispatch", "engine.decode"), ("decode.advance", "engine.decode"),
    ("prefill.wait", "engine.prefill"), ("prefill.index", "engine.prefill"),
    ("engine.prefill", "sched.admit"), ("sched.admit", "sched.step"),
    ("sched.harvest", "sched.step"), ("sched.gauges", "sched.step"),
    ("sched.expire", "sched.step"), ("engine.release", "sched.step")])
def test_nesting_in_the_profile(profiled, inner, outer):
    reduced, _, _ = profiled
    spans = trace_reduce.host_spans(reduced, PREFIX)
    outers = [s for s in spans if s[0] == PREFIX + outer]
    inners = [s for s in spans if s[0] == PREFIX + inner]
    assert inners
    for s in inners:
        assert any(inside(s, o) for o in outers), (s, outer)


def test_a_decode_step_lies_in_a_scheduler_step_or_a_prefix_hit(profiled):
    reduced, _, _ = profiled
    spans = trace_reduce.host_spans(reduced, PREFIX)
    steps = [s for s in spans if s[0] == PREFIX + "sched.step"]
    prefills = [s for s in spans if s[0] == PREFIX + "engine.prefill"]
    decodes = [s for s in spans if s[0] == PREFIX + "engine.decode"]
    assert all(any(inside(d, s) for s in steps) for d in decodes)
    # the third request's un-shared tail went through the decode program
    assert any(inside(d, p) for d in decodes for p in prefills)
    assert not all(any(inside(d, p) for p in prefills) for d in decodes)


def test_one_prefill_an_admission_and_one_release_a_retirement(profiled):
    _, events, counters = profiled
    count = lambda name: sum(n == PREFIX + name for n, *_ in events)
    assert counters["serve/admitted"] == 3
    assert count("engine.prefill") == count("sched.admit") == 3
    assert count("engine.release") == counters["serve/retired"] == 3
    assert count("sched.submit") == 3
    # the prefix hit's tail steps come on top of the scheduler's own
    assert count("engine.decode") > counters["serve/decode_steps"]
    assert count("decode.wait") == count("engine.decode")


def test_identifiers_arrive_as_stats_of_the_events(profiled):
    _, events, _ = profiled
    stats = lambda name: [st for n, _, _, st in events if n == PREFIX + name]
    assert sorted(st["request_id"] for st in stats("sched.submit")) \
        == sorted(st["request_id"] for st in stats("sched.admit")) \
        == [0, 1, 2]
    for st in stats("sched.admit"):
        assert set(st) == {"request_id", "slot", "prompt_len"}
        assert st["slot"] in (0, 1)
    assert {st["prompt_len"] for st in stats("sched.admit")} == {6, 3, 5}
    assert all("slot" in st for st in stats("engine.prefill"))
    assert all("slot" in st for st in stats("engine.release"))
    assert all(st["active"] in (1, 2) for st in stats("engine.decode"))
    # the paged kernel's walk beside the table it used to walk: two slots
    # of 24 / 4 blocks, contexts of 3 to 9 positions
    assert all(st["table_blocks"] == 12 and 1 <= st["live_blocks"] <= 5
               for st in stats("engine.decode"))
    steps = [st["step"] for st in stats("sched.step")]
    assert steps == sorted(steps) and steps[0] == 0


def test_recording_gives_the_same_names_with_parents_and_ids(profiled,
                                                             recorded):
    reduced, _, counters = profiled
    spans, counters_again = recorded
    assert counters_again["serve/decode_steps"] \
        == counters["serve/decode_steps"]
    names = {s.name for s in spans}
    # float32 stored, float32 compute: the weights' image is the tree
    assert names == SERVED | BUILD - {"compile.verify", "compile.image"}
    in_profile = [n[len(PREFIX):] for n, _, _ in
                  trace_reduce.host_spans(reduced, PREFIX)]
    for name in SERVED:
        assert sum(s.name == name for s in spans) == in_profile.count(name)
    parents = {}
    for s in spans:
        parents.setdefault(s.name, set()).add(s.parent and s.parent[0])
    assert parents["sched.step"] == parents["sched.submit"] == {None}
    assert parents["engine.build"] == {None}
    assert parents["decode.wait"] == {"engine.decode"}
    assert parents["engine.decode"] == {"sched.step", "engine.prefill"}
    assert parents["engine.prefill"] == {"sched.admit"}
    assert parents["sched.admit"] == {"sched.step"}
    assert parents["compile.decode"] == parents["engine.lint"] \
        == {"engine.build"}
    # a parent is the enclosing span itself: its name and its start
    started = {(s.name, s.start) for s in spans}
    assert all(s.parent in started for s in spans if s.parent)
    admits = [s for s in spans if s.name == "sched.admit"]
    assert sorted(s.ids["request_id"] for s in admits) == [0, 1, 2]
    assert all(set(s.ids) == {"request_id", "slot", "prompt_len"}
               for s in admits)
    assert all(s.start <= s.end for s in spans)


def test_with_both_off_nothing_is_recorded(model_params):
    assert not trace.spans_enabled()
    serve(paged_engine(model_params))
    assert trace.drain_spans() == []


def test_a_speculative_engine_over_its_default_pool_has_the_same_cut(
        model_params):
    model, params = model_params
    with trace.span_recording():
        engine = ServingEngine(model, params, max_seqs=2, max_len=24,
                               prefill_len=8, speculate_k=2)
        sched = SlotScheduler(engine, registry=MetricsRegistry(),
                              speculate_k=2)
        sched.run([Request(prompt=[1, 2, 3, 1, 2, 3], max_new_tokens=5)])
        names = {s.name for s in trace.drain_spans()}
    # no plain decode step (no prefix was shared, so no tail went through
    # one); no cast program (the weights' image is the tree)
    assert names == set(trace.SPANS) - {
        "compile.image", "engine.decode", "decode.plan", "decode.dispatch",
        "decode.advance", "decode.wait"}


def test_an_engine_with_buckets_names_the_bucket_and_its_tokens(
        model_params):
    model, params = model_params
    engine = PagedServingEngine(model, params, max_seqs=2, max_len=24,
                                prefill_len=[4, 8], num_blocks=16,
                                block_size=4, cache_dtype=jnp.float32)
    with trace.span_recording():
        serve(engine)
        spans = trace.drain_spans()
    assert sorted((s.ids["bucket"], s.ids["tokens"]) for s in spans
                  if s.name == "prefill.dispatch") == [(4, 3), (8, 6)]


def test_an_unknown_name_is_an_error_only_while_recording():
    with trace.span("no.such.span"):
        pass
    with trace.span_recording():
        with pytest.raises(ValueError, match="SPANS"):
            trace.span("no.such.span")
        # a Timer names itself: its spans reach the buffer through the
        # hook, exempt from the table
        t = Timer("my-own-timer")
        t.start()
        t.stop()
        span, = trace.drain_spans()
    assert span == trace.Span("my-own-timer", span.start, span.end)
    assert span.parent is None and span.ids is None


def test_chrome_events_carry_ids_and_parent():
    with trace.span_recording():
        with trace.span("sched.step", step=4):
            with trace.span("engine.decode", active=2):
                pass
        inner, outer = trace.drain_spans()
    ev_in, ev_out = trace.chrome_trace_events([inner, outer], step=9)
    assert ev_out["args"] == {"step": 4}           # the span's own wins
    assert ev_in["args"] == {"active": 2, "step": 9, "parent": "sched.step",
                             "parent_ts": outer.start * 1e6}
    assert ev_in["ts"] >= ev_out["ts"]
    plain, = trace.chrome_trace_events([trace.Span("t", 1.0, 2.0)])
    assert "args" not in plain


def test_programs_are_the_same_with_recording_on(model_params):
    built = []
    for recording in (False, True):
        if recording:
            trace.enable_spans()
        try:
            built.append(paged_engine(model_params))
        finally:
            trace.disable_spans()
    off, on = built
    for name in ("prefill_compiled", "decode_compiled", "release_compiled"):
        assert program_text(getattr(off, name)) == \
            program_text(getattr(on, name))


def test_host_tracer_level_reaches_the_profiler(tmp_path):
    for level, expected in ((0, 0), (1, 1)):
        log_dir = str(tmp_path / str(level))
        with profile_trace(log_dir, host_tracer_level=level):
            with trace.span("sched.step", step=0):
                pass
        path, = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        assert len(host_events(path)) == expected


def test_the_table_is_the_set_of_names_the_code_uses():
    used = set()
    for rel in ("apex_tpu/serving/scheduler.py", "apex_tpu/serving/engine.py"):
        with open(os.path.join(ROOT, rel)) as f:
            used |= set(re.findall(r'\bspan\("([a-z.]+)"', f.read()))
    assert used == set(trace.SPANS)


@pytest.mark.parametrize("doc", ["PERF.md", "docs/OBSERVABILITY.md"])
def test_documents_copy_the_table(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    missing = [n for n in trace.SPANS if f"`{n}`" not in text]
    assert not missing, f"{doc} does not name {missing}"

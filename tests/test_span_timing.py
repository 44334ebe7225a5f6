"""The serving loop's phase histograms (``trace.TIMED``,
``serve/span_ms/<name>``): what each timed span observes, where the
prefill's wait for the decode step in flight (``prefill.drain``) occurs,
what an engine outside a scheduler times (nothing), and that the timing
leaves the served tokens and the AOT programs as they were."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import TraceAnnotation

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.observability import trace
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.serving import PagedServingEngine, Request, SlotScheduler

from _program_text import program_text

# the third prompt shares the first's first block: a prefix hit, whose
# tail goes through the decode program inside engine.prefill
TIMED_PREFIX = "serve/span_ms/"
REQUESTS = (([1, 2, 3, 4, 5, 6], 3), ([7, 8, 9], 4), ([1, 2, 3, 4, 9], 2),
            ([5, 5, 5], 3), ([11, 12], 5))


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


def serve(engine, registry=None):
    """Five requests on two slots through the scheduler; the streams by
    request id and the scheduler's registry."""
    registry = MetricsRegistry() if registry is None else registry
    sched = SlotScheduler(engine, registry=registry)
    out = sched.run([Request(prompt=p, max_new_tokens=n)
                     for p, n in REQUESTS])
    return {i: c.tokens for i, c in out.items()}, registry.snapshot()


def timed(snap, name, stat):
    return snap[f"{TIMED_PREFIX}{name}_{stat}"]


@pytest.fixture(scope="module")
def recorded(model_params):
    """One served run under span_recording(): the recorded spans and the
    scheduler's registry."""
    engine = paged_engine(model_params)
    with trace.span_recording():
        streams, snap = serve(engine)
        spans = trace.drain_spans()
    return spans, snap, streams


def test_timed_is_a_subset_of_the_table():
    assert set(trace.TIMED) <= set(trace.SPANS)
    assert len(set(trace.TIMED)) == len(trace.TIMED) == 6


@pytest.mark.parametrize("name", trace.TIMED)
def test_each_timed_span_grows_its_histogram_once_an_occurrence(recorded,
                                                                name):
    spans, snap, _ = recorded
    occurred = [s for s in spans if s.name == name]
    assert occurred
    assert timed(snap, name, "count") == len(occurred)


@pytest.mark.parametrize("name", trace.TIMED)
def test_the_sums_are_the_recorded_lengths(recorded, name):
    """Under span_recording() one pair of stamps feeds the buffer and the
    histogram: the sums agree to 1 us an occurrence."""
    spans, snap, _ = recorded
    lengths_ms = [(s.end - s.start) * 1e3 for s in spans if s.name == name]
    assert abs(timed(snap, name, "sum") - sum(lengths_ms)) \
        <= 1e-3 * len(lengths_ms)


def test_three_observations_a_step_and_three_an_admission(recorded):
    _, snap, _ = recorded
    steps = timed(snap, "sched.step", "count")
    assert steps >= snap["serve/decode_steps"]
    assert timed(snap, "sched.admit", "count") \
        == timed(snap, "engine.prefill", "count") == snap["serve/admitted"]
    # a decode read by each engine.decode that had one in flight, by a
    # settle, or at once (a prefix tail's): never more reads than steps
    assert 0 < timed(snap, "decode.wait", "count") \
        <= timed(snap, "engine.decode", "count")
    for name in trace.TIMED:
        assert timed(snap, name, "sum") >= 0.0


def test_a_prefill_drains_only_a_step_in_flight(model_params):
    """The first admission of an idle engine has nothing to wait for; an
    admission after a decode step waits for it under prefill.drain, and
    the step's tokens stay held for the next read."""
    engine = paged_engine(model_params)
    reg = MetricsRegistry()
    drains = lambda: reg.snapshot().get(
        f"{TIMED_PREFIX}prefill.drain_count")
    n = engine.max_seqs
    with trace.timing_into(reg):
        first = engine.prefill([1, 2, 3], 0)
        assert drains() == 0
        tokens = np.zeros(n, np.int32)
        tokens[0] = first
        active = np.array([True, False])
        engine.decode(tokens, np.zeros(n, np.float32), active)
        engine.prefill([7, 8, 9], 1)
        assert drains() == 1
        # the drained step is still the one the next read returns
        held = engine.settle()
        assert held[0] >= 0          # a token, not NO_TOKEN
        # nothing in flight now: a prefill after a settle drains nothing
        engine.release_slot(1)
        engine.prefill([4, 5], 1)
        assert drains() == 1


def test_no_registry_attached_times_nothing(model_params):
    """An engine driven outside a scheduler (and outside timing_into)
    observes into no registry, and its spans are the bare annotation."""
    engine = paged_engine(model_params)
    reg = MetricsRegistry()
    timing = trace.timing_into(reg)
    before = reg.snapshot()
    assert before[f"{TIMED_PREFIX}engine.prefill_count"] == 0
    tok = engine.prefill([1, 2, 3], 0)
    engine.decode(np.array([tok, 0], np.int32), np.zeros(2, np.float32),
                  np.array([True, False]))
    engine.settle()
    assert reg.snapshot() == before
    assert type(trace.span("engine.decode")) is TraceAnnotation
    with timing:
        assert type(trace.span("engine.decode")) is not TraceAnnotation
        # a name outside TIMED stays the bare annotation
        assert type(trace.span("decode.plan")) is TraceAnnotation
    assert type(trace.span("engine.decode")) is TraceAnnotation
    fresh = MetricsRegistry()
    assert not [n for n in fresh.snapshot() if n.startswith("serve/")]


def test_a_scheduler_step_leaves_nothing_attached_even_when_it_fails(
        model_params, monkeypatch):
    engine = paged_engine(model_params)
    sched = SlotScheduler(engine, registry=MetricsRegistry())
    sched.submit(Request(prompt=[1, 2, 3], max_new_tokens=4))

    def broken(*args, **kw):
        raise RuntimeError("decode fault")

    monkeypatch.setattr(engine, "decode", broken)
    with pytest.raises(RuntimeError, match="decode fault"):
        sched.step()
    assert type(trace.span("engine.decode")) is TraceAnnotation


def test_nested_attachments_restore_the_outer_one():
    outer, inner = MetricsRegistry(), MetricsRegistry()
    with trace.timing_into(outer):
        with trace.timing_into(inner):
            with trace.span("sched.step", step=0):
                pass
        with trace.span("sched.step", step=1):
            pass
    count = lambda reg: reg.snapshot()[
        f"{TIMED_PREFIX}sched.step_count"]
    assert count(outer) == count(inner) == 1


def test_served_streams_are_the_same_without_the_timing(model_params,
                                                        recorded,
                                                        monkeypatch):
    _, _, streams = recorded
    monkeypatch.setattr(trace, "TIMED", ())
    plain, snap = serve(paged_engine(model_params))
    assert plain == streams
    assert not [n for n in snap if n.startswith(TIMED_PREFIX)]


def test_programs_are_the_same_with_a_registry_attached(model_params):
    built = []
    for attached in (False, True):
        if attached:
            with trace.timing_into(MetricsRegistry()):
                built.append(paged_engine(model_params))
        else:
            built.append(paged_engine(model_params))
    off, on = built
    for name in ("prefill_compiled", "decode_compiled", "release_compiled"):
        assert program_text(getattr(off, name)) == \
            program_text(getattr(on, name))


def test_the_block_walk_runs_only_where_a_span_is_seen(model_params,
                                                      monkeypatch):
    """engine.decode's live_blocks / table_blocks keywords cost a walk of
    the block tables; nothing reads them unless spans are recorded or a
    profiler session takes host events."""
    engine = paged_engine(model_params)
    walk, calls = engine.allocator.walk_blocks, []

    def counted():
        calls.append(1)
        return walk()

    monkeypatch.setattr(engine.allocator, "walk_blocks", counted)
    assert not trace.spans_seen()
    _, snap = serve(engine)
    assert snap["serve/decode_steps"] > 0 and calls == []
    with trace.span_recording():
        assert trace.spans_seen()
        _, snap = serve(engine)
        decodes = [s for s in trace.drain_spans()
                   if s.name == "engine.decode"]
    assert len(calls) == len(decodes) > 0
    assert all({"live_blocks", "table_blocks"} <= set(s.ids)
               for s in decodes)

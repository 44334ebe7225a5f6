"""Request-lifecycle tracing, latency percentiles, and SLO goodput
(docs/OBSERVABILITY.md "Serving latency & SLO", docs/SERVING.md):
histogram percentile math vs numpy, the bounded request ring + its
concurrency contract, strict-JSON Chrome swimlane export, measured
scheduler latencies, SLO goodput/burn-rate + the flight-recorder dump,
and the tracing-off zero-cost assertions."""

import io
import json
import math
import threading

import numpy as np

import jax
import pytest

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.observability import JSONLSink, StepReporter
from apex_tpu.observability.registry import (Histogram, MetricsRegistry,
                                             log_buckets)
from apex_tpu.observability.reqtrace import (LATENCY_BUCKETS_MS,
                                             RequestRecord, RequestTrace,
                                             chrome_request_trace)
from apex_tpu.observability.slo import (SLOTarget, SLOTracker,
                                        SLOViolationError)
from apex_tpu.serving import Request, ServingEngine, SlotScheduler

from _program_text import program_text


# ---------------------------------------------------------------------------
# log-spaced buckets + percentile readout
# ---------------------------------------------------------------------------

class TestLogBuckets:
    def test_endpoints_count_and_monotone(self):
        b = log_buckets(0.1, 1000.0, 9)
        assert len(b) == 9
        assert b[0] == pytest.approx(0.1) and b[-1] == pytest.approx(1000.0)
        assert all(hi > lo for lo, hi in zip(b, b[1:]))
        # constant ratio — the documented resolution property
        ratios = [hi / lo for lo, hi in zip(b, b[1:])]
        np.testing.assert_allclose(ratios, ratios[0], rtol=1e-12)

    def test_validation(self):
        for lo, hi, n in ((0.0, 1.0, 4), (-1.0, 1.0, 4), (2.0, 1.0, 4),
                          (1.0, 2.0, 1)):
            with pytest.raises(ValueError):
                log_buckets(lo, hi, n)


class TestHistogramPercentile:
    @pytest.mark.parametrize("dist", ["lognormal", "uniform"])
    def test_vs_numpy_quantile_within_bucket_resolution(self, dist):
        """The documented error bound: a percentile interpolated inside
        one log bucket is within (r - 1) relative of numpy's exact
        quantile, r the adjacent-bound ratio."""
        rng = np.random.RandomState(0)
        if dist == "lognormal":
            samples = rng.lognormal(3.0, 1.0, 5000)
        else:
            samples = rng.uniform(2.0, 500.0, 5000)
        bounds = log_buckets(samples.min() * 0.9, samples.max() * 1.1, 200)
        r = (bounds[-1] / bounds[0]) ** (1.0 / (len(bounds) - 1))
        h = Histogram("x", bounds)
        for s in samples:
            h.observe(s)
        for q in (1, 25, 50, 90, 95, 99, 99.9):
            true = float(np.percentile(samples, q))
            assert abs(h.percentile(q) - true) <= (r - 1.0) * true + 1e-9

    def test_small_windows_track_numpy_convention(self):
        """The bench legs read p95/p99 off a handful of requests: at
        small n the estimator must follow numpy's rank convention (an
        outlier max must not swallow p95), staying inside the (r - 1)
        relative bound."""
        rng = np.random.RandomState(7)
        bounds = log_buckets(1e-2, 6e4, 68)
        r = (bounds[-1] / bounds[0]) ** (1.0 / (len(bounds) - 1))
        for _ in range(200):
            n = rng.randint(2, 40)
            samples = np.clip(
                rng.lognormal(rng.uniform(1, 8), rng.uniform(0.3, 2), n),
                bounds[0], bounds[-1])
            h = Histogram("x", bounds)
            for s in samples:
                h.observe(s)
            for q in (5, 50, 95, 99):
                true = float(np.percentile(samples, q))
                assert abs(h.percentile(q) - true) <= (r - 1) * true + 1e-9
        # the outlier shape: one huge sample must not drag p95 to it
        s = np.concatenate([rng.uniform(100, 5000, 17), [24000.0]])
        h = Histogram("x", bounds)
        for v in s:
            h.observe(v)
        assert abs(h.percentile(95) - np.percentile(s, 95)) \
            <= (r - 1) * np.percentile(s, 95)

    def test_edges(self):
        h = Histogram("x", log_buckets(1.0, 100.0, 10))
        assert math.isnan(h.percentile(50))  # empty
        h.observe(7.0)
        for q in (0, 50, 100):  # single sample: every quantile is it
            assert h.percentile(q) == 7.0
        h.observe(70.0)
        assert h.percentile(0) == 7.0 and h.percentile(100) == 70.0
        # monotone in q
        qs = [h.percentile(q) for q in range(0, 101, 5)]
        assert all(b >= a for a, b in zip(qs, qs[1:]))
        with pytest.raises(ValueError):
            h.percentile(101)
        with pytest.raises(ValueError):
            h.percentile(-1)

    def test_out_of_range_samples_clamp_to_observed(self):
        """Samples past the last bound (the +inf overflow bucket) and
        below the first bound still yield finite percentiles clamped to
        the observed min/max — no fabricated +inf p99."""
        h = Histogram("x", log_buckets(1.0, 10.0, 5))
        for v in (0.01, 0.02, 5.0, 500.0, 900.0):
            h.observe(v)
        assert h.percentile(0) == 0.01
        assert h.percentile(99) <= 900.0
        assert h.percentile(100) == 900.0
        assert math.isfinite(h.percentile(90))

    def test_reset_clears_percentile_state(self):
        h = Histogram("x", log_buckets(1.0, 10.0, 5))
        h.observe(3.0)
        h.reset()
        assert math.isnan(h.percentile(50))
        h.observe(9.0)
        assert h.percentile(50) == 9.0


# ---------------------------------------------------------------------------
# Prometheus text-format snapshot
# ---------------------------------------------------------------------------

class TestRenderPrometheus:
    def test_counter_gauge_histogram_series(self):
        reg = MetricsRegistry()
        reg.counter("serve/admitted").inc(3)
        reg.gauge("slo/goodput").set(0.97)
        reg.gauge("never/set")  # unset: must not render
        reg.histogram("serve/ttft_ms", (1.0, 10.0)).observe(5.0)
        text = reg.render_prometheus()
        lines = text.splitlines()
        assert "# TYPE serve_admitted counter" in lines
        assert "serve_admitted 3" in lines
        assert "# TYPE slo_goodput gauge" in lines
        assert "slo_goodput 0.97" in lines
        assert not any("never" in ln for ln in lines)
        assert "# TYPE serve_ttft_ms histogram" in lines
        assert 'serve_ttft_ms_bucket{le="1"} 0' in lines
        assert 'serve_ttft_ms_bucket{le="10"} 1' in lines
        assert 'serve_ttft_ms_bucket{le="+Inf"} 1' in lines
        assert "serve_ttft_ms_sum 5" in lines
        assert "serve_ttft_ms_count 1" in lines
        assert text.endswith("\n")

    def test_nonfinite_gauge_spellings(self):
        reg = MetricsRegistry()
        reg.gauge("a").set(float("nan"))
        reg.gauge("b").set(float("inf"))
        text = reg.render_prometheus()
        assert "a NaN" in text and "b +Inf" in text

    def test_empty_registry(self):
        assert MetricsRegistry().render_prometheus() == ""


# ---------------------------------------------------------------------------
# request records + the bounded ring
# ---------------------------------------------------------------------------

def _rec(rid, slot=0, submit=0.0, admit=0.002, first=0.012, last=0.052,
         retire=0.052, generated=5, reason="length", ticks=()):
    r = RequestRecord(request_id=rid, prompt_len=3, submit_t=submit,
                      admit_t=admit, prefill_done_t=first,
                      first_token_t=first, last_token_t=last,
                      retire_t=retire, slot=slot, generated=generated,
                      finish_reason=reason)
    r.decode_ts.extend(ticks)
    return r


class TestRequestRecord:
    def test_derived_latencies(self):
        r = _rec(0)
        assert r.queue_wait_ms == pytest.approx(2.0)
        assert r.ttft_ms == pytest.approx(12.0)
        assert r.e2e_ms == pytest.approx(52.0)
        # 5 tokens, 40 ms from first to last -> 10 ms/token after first
        assert r.tpot_ms == pytest.approx(10.0)

    def test_unstamped_transitions_are_none(self):
        r = RequestRecord(request_id=1, prompt_len=2, submit_t=1.0)
        assert r.queue_wait_ms is None and r.ttft_ms is None
        assert r.tpot_ms is None and r.e2e_ms is None

    def test_single_token_has_no_tpot(self):
        assert _rec(0, generated=1).tpot_ms is None

    def test_to_dict_is_strict_json(self):
        doc = _rec(3, ticks=[0.02, 0.03]).to_dict()
        parsed = json.loads(json.dumps(doc, allow_nan=False))
        assert parsed["request_id"] == 3
        assert parsed["decode_ts"] == [0.02, 0.03]
        assert parsed["tpot_ms"] == pytest.approx(10.0)


class TestRequestTrace:
    def test_overflow_evicts_oldest(self):
        trace = RequestTrace(capacity=3)
        for i in range(5):
            trace.append(_rec(i))
        assert len(trace) == 3
        assert [r.request_id for r in trace.records()] == [2, 3, 4]
        assert [r.request_id for r in trace.last(2)] == [3, 4]
        assert trace.last(0) == []
        assert [r.request_id for r in trace.last(99)] == [2, 3, 4]

    def test_drain_empties_exactly_once(self):
        trace = RequestTrace(capacity=8)
        trace.append(_rec(0))
        assert [r.request_id for r in trace.drain()] == [0]
        assert trace.drain() == [] and len(trace) == 0

    def test_capacity_validation(self):
        with pytest.raises(ValueError):
            RequestTrace(capacity=0)

    def test_concurrent_append_drain_and_hook_loses_nothing(self):
        """Mirror of the PR 3 record_span/drain_spans concurrency test:
        producer threads hammer append while a drainer races drain and a
        StepReporter hook (the SLO tracker reading last(n)) runs
        alongside — within capacity, every record comes out exactly
        once."""
        n_producers, per_producer = 4, 200
        trace = RequestTrace(capacity=n_producers * per_producer)
        tracker = SLOTracker([SLOTarget("ttft_ms", 95, 1000.0)],
                             registry=MetricsRegistry(), trace=trace,
                             on_violation="skip")
        reporter = StepReporter([JSONLSink(io.StringIO())],
                                registry=MetricsRegistry(),
                                hooks=[tracker])
        drained, stop = [], threading.Event()

        def produce(k):
            for i in range(per_producer):
                trace.append(_rec(k * per_producer + i, slot=k))

        def drain_loop():
            while not stop.is_set():
                drained.extend(trace.drain())

        def report_loop():
            step = 0
            while not stop.is_set():
                reporter.report(step, metrics={"x": 0.0})
                step += 1

        threads = ([threading.Thread(target=produce, args=(k,))
                    for k in range(n_producers)]
                   + [threading.Thread(target=drain_loop),
                      threading.Thread(target=report_loop)])
        for t in threads:
            t.start()
        for t in threads[:n_producers]:
            t.join()
        stop.set()
        for t in threads[n_producers:]:
            t.join()
        drained.extend(trace.drain())
        ids = sorted(r.request_id for r in drained)
        assert ids == list(range(n_producers * per_producer))


# ---------------------------------------------------------------------------
# Chrome swimlane export
# ---------------------------------------------------------------------------

class TestChromeRequestTrace:
    def test_strict_json_one_lane_per_slot_with_flows(self):
        records = [_rec(0, slot=0), _rec(1, slot=1, submit=0.1, admit=0.11,
                                         first=0.12, last=0.2, retire=0.2),
                   _rec(2, slot=0, submit=0.3, admit=0.31, first=0.32,
                        last=0.4, retire=0.4, ticks=[0.35, 0.4])]
        doc = chrome_request_trace(records, pid=7)
        # strict JSON: round-trips without NaN allowances
        doc2 = json.loads(json.dumps(doc, allow_nan=False))
        events = doc2["traceEvents"]
        lanes = {e["args"]["name"] for e in events
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert lanes == {"queue", "slot 0", "slot 1"}
        spans = [e for e in events if e["ph"] == "X"]
        # one queue span + one slot span per record
        assert sum(1 for e in spans if e["tid"] == 0) == 3
        by_slot = {e["args"]["request_id"]: e["tid"]
                   for e in spans if e["tid"] > 0}
        assert by_slot == {0: 1, 1: 2, 2: 1}
        # the slot span carries the latency vocabulary
        slot_span = next(e for e in spans
                         if e["tid"] > 0 and e["args"]["request_id"] == 0)
        for key in ("queue_wait_ms", "ttft_ms", "tpot_ms", "e2e_ms",
                    "prompt_len", "generated", "finish_reason"):
            assert key in slot_span["args"]
        # flow events pair up (start on the queue lane, finish on slot)
        starts = [e for e in events if e["ph"] == "s"]
        finishes = [e for e in events if e["ph"] == "f"]
        assert len(starts) == len(finishes) == 3
        assert {e["id"] for e in starts} == {0, 1, 2}
        assert all(e["tid"] == 0 for e in starts)
        # decode ticks render as instants on the owning slot lane
        ticks = [e for e in events if e["name"] == "tick"]
        assert len(ticks) == 2 and all(e["tid"] == 1 for e in ticks)
        assert all(e["pid"] == 7 for e in events)

    def test_mid_flight_records_render_queue_span_only(self):
        r = RequestRecord(request_id=9, prompt_len=2, submit_t=1.0,
                          admit_t=1.1, slot=0)
        doc = chrome_request_trace([r])
        spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        assert len(spans) == 1 and spans[0]["tid"] == 0
        json.loads(json.dumps(doc, allow_nan=False))

    def test_ticks_off(self):
        doc = chrome_request_trace([_rec(0, ticks=[0.02])], ticks=False)
        assert not [e for e in doc["traceEvents"] if e["name"] == "tick"]


# ---------------------------------------------------------------------------
# the scheduler measures, the engine stays untouched
# ---------------------------------------------------------------------------

def _tiny_model():
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=64)
    model = GPTModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def model_params():
    return _tiny_model()


@pytest.fixture(scope="module")
def engine(model_params):
    model, params = model_params
    return ServingEngine(model, params, max_seqs=2, max_len=32,
                         prefill_len=8)


class TestSchedulerLifecycle:
    def test_completions_carry_measured_latencies(self, engine):
        reg = MetricsRegistry()
        sched = SlotScheduler(engine, registry=reg)
        # 2 slots, 3 requests: the third queues behind a whole generation
        out = sched.run([Request(prompt=[1 + i, 2], max_new_tokens=4)
                         for i in range(3)])
        assert sorted(out) == [0, 1, 2]
        for c in out.values():
            assert c.queue_wait_ms is not None and c.queue_wait_ms >= 0.0
            assert c.ttft_ms >= c.queue_wait_ms
            assert c.e2e_ms >= c.ttft_ms
            assert c.tpot_ms is not None and c.tpot_ms > 0.0
        # queue wait is MEASURED from submit: the queued request waited
        # out at least one whole earlier generation, the admitted-
        # immediately ones did not
        assert out[2].queue_wait_ms > max(out[0].queue_wait_ms,
                                          out[1].queue_wait_ms)

    def test_single_token_completion_has_no_tpot(self, engine):
        sched = SlotScheduler(engine, registry=MetricsRegistry())
        out = sched.run([Request(prompt=[5], max_new_tokens=1)])
        (c,) = out.values()
        assert c.tpot_ms is None and c.ttft_ms is not None

    def test_latency_histograms_populated(self, engine):
        reg = MetricsRegistry()
        sched = SlotScheduler(engine, registry=reg)
        sched.run([Request(prompt=[1 + i], max_new_tokens=3)
                   for i in range(4)])
        for name in ("serve/queue_wait_ms", "serve/ttft_ms",
                     "serve/tpot_ms", "serve/e2e_ms"):
            h = reg.histogram(name, LATENCY_BUCKETS_MS)
            assert h.count == 4, name
            assert math.isfinite(h.percentile(99))
        # and the whole surface exports as a Prometheus snapshot
        text = reg.render_prometheus()
        assert "serve_ttft_ms_count 4" in text
        assert 'serve_ttft_ms_bucket{le="+Inf"} 4' in text

    def test_trace_ring_and_chrome_export(self, engine):
        trace = RequestTrace(capacity=16)
        sched = SlotScheduler(engine, registry=MetricsRegistry(),
                              trace=trace)
        out = sched.run([Request(prompt=[1 + i, 2], max_new_tokens=3)
                         for i in range(3)])
        assert len(trace) == 3
        for r in trace.records():
            # ticks captured: 3 tokens = 1 prefill sample + 2 decode ticks
            assert len(r.decode_ts) == len(out[r.request_id].tokens) - 1
            assert r.finish_reason == "length" and r.slot in (0, 1)
        doc = trace.chrome_trace()
        doc2 = json.loads(json.dumps(doc, allow_nan=False))
        lanes = {e["args"]["name"] for e in doc2["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "thread_name"}
        assert lanes == {"queue", "slot 0", "slot 1"}

    def test_untraced_scheduler_keeps_no_ticks(self, engine):
        sched = SlotScheduler(engine, registry=MetricsRegistry())
        sched.submit(Request(prompt=[1], max_new_tokens=3))
        while sched.pending:
            sched.step()
            for st in sched.active.values():
                assert st.record.decode_ts == []


class TestTracingZeroCost:
    def test_device_programs_byte_identical_and_no_recompiles(
            self, model_params):
        """The acceptance contract: tracing on vs off changes NOTHING on
        the device — the three AOT serving programs are byte-identical,
        and a fully-traced run (ring + SLO tracker) stays flat under the
        recompile guard (PR 11), the way PR 1/PR 3 assert their
        zero-cost modes."""
        model, params = model_params

        def build():
            return ServingEngine(model, params, max_seqs=2, max_len=16,
                                 prefill_len=4)

        # one call site for both: the compiled text carries the line and
        # column of every frame that led to the trace
        eng_off, eng_on = [build() for _ in range(2)]
        reqs = [Request(prompt=[1 + i, 2], max_new_tokens=3)
                for i in range(3)]
        sched_off = SlotScheduler(eng_off, registry=MetricsRegistry())
        reg = MetricsRegistry()
        trace = RequestTrace(capacity=8)
        tracker = SLOTracker([SLOTarget("ttft_ms", 95, 5000.0)],
                             registry=reg, trace=trace,
                             on_violation="skip")
        sched_on = SlotScheduler(eng_on, registry=reg, trace=trace,
                                 slo=tracker)
        # no_recompile=True wraps each loop in recompile_guard — a
        # tracing-induced compile or transfer-triggering retrace raises
        out_off = sched_off.run(reqs, no_recompile=True)
        out_on = sched_on.run([Request(prompt=list(r.prompt),
                                       max_new_tokens=r.max_new_tokens)
                               for r in reqs], no_recompile=True)
        # same programs, byte for byte
        for a, b in ((eng_off.prefill_compiled, eng_on.prefill_compiled),
                     (eng_off.decode_compiled, eng_on.decode_compiled),
                     (eng_off.release_compiled, eng_on.release_compiled)):
            assert program_text(a) == program_text(b)
        # and identical greedy token streams — tracing observed, never
        # perturbed
        for rid in out_off:
            assert out_off[rid].tokens == out_on[rid].tokens


# ---------------------------------------------------------------------------
# SLO targets, goodput, burn rate, flight recorder
# ---------------------------------------------------------------------------

class TestSLOTarget:
    def test_validation(self):
        with pytest.raises(ValueError, match="metric"):
            SLOTarget("latency", 95, 100.0)
        with pytest.raises(ValueError, match="quantile"):
            SLOTarget("ttft_ms", 100.0, 100.0)
        with pytest.raises(ValueError, match="threshold"):
            SLOTarget("ttft_ms", 95, 0.0)

    def test_describe_and_budget(self):
        t = SLOTarget("ttft_ms", 95, 200.0)
        assert t.describe() == "ttft_ms p95 <= 200ms"
        assert t.error_budget == pytest.approx(0.05)


def _tracker(targets, trace=None, **kw):
    return SLOTracker(targets, registry=MetricsRegistry(), trace=trace,
                      **kw)


class TestSLOTracker:
    def test_goodput_counts_requests_meeting_all_targets(self):
        tr = _tracker([SLOTarget("ttft_ms", 95, 15.0),
                       SLOTarget("tpot_ms", 99, 12.0)],
                      on_violation="skip")
        assert math.isnan(tr.goodput())
        # rec: ttft 12ms tpot 10ms -> good; push 8 good + 2 bad-ttft
        for i in range(8):
            tr.observe(_rec(i))
        for i in range(2):
            tr.observe(_rec(10 + i, first=0.020, last=0.060, retire=0.060))
        assert tr.goodput() == pytest.approx(0.8)
        reg = tr._reg
        snap = reg.snapshot()
        assert snap["slo/goodput"] == pytest.approx(0.8)
        assert snap["slo/window_requests"] == 10.0

    def test_burn_rate_is_violation_fraction_over_budget(self):
        target = SLOTarget("ttft_ms", 90, 15.0)  # budget 10%
        tr = _tracker([target], on_violation="skip")
        for i in range(9):
            tr.observe(_rec(i))                      # ttft 12 -> ok
        tr.observe(_rec(9, first=0.020, retire=0.060))  # ttft 20 -> over
        # 10% violating / 10% budget = burning exactly the budget
        assert tr.burn_rate(target) == pytest.approx(1.0)
        assert tr._reg.snapshot()["slo/burn_rate"] == pytest.approx(1.0)

    def test_window_percentile_matches_numpy(self):
        target = SLOTarget("e2e_ms", 95, 1000.0)
        tr = _tracker([target], on_violation="skip")
        vals = np.random.RandomState(0).uniform(10, 90, 40)
        for i, v in enumerate(vals):
            tr.observe(_rec(i, retire=v / 1e3))
        assert tr.window_percentile(target) == pytest.approx(
            float(np.percentile(vals, 95)))

    def test_undefined_metric_neither_helps_nor_hurts(self):
        tr = _tracker([SLOTarget("tpot_ms", 99, 1.0)], on_violation="skip")
        tr.observe(_rec(0, generated=1))  # no tpot on 1-token requests
        assert tr.goodput() == 1.0  # vacuously good
        assert math.isnan(tr.burn_rate(tr.targets[0]))
        assert not tr.violating_targets()

    def test_rolling_window_evicts(self):
        tr = _tracker([SLOTarget("ttft_ms", 95, 15.0)], window=4,
                      on_violation="skip")
        for i in range(4):  # all bad
            tr.observe(_rec(i, first=0.020, retire=0.060))
        assert tr.goodput() == 0.0
        for i in range(4):  # window rolls over to all good
            tr.observe(_rec(10 + i))
        assert tr.goodput() == 1.0

    def test_forced_violation_writes_flight_recorder_dump(self, tmp_path):
        """The acceptance test: a violating window + a report hook call
        produce a strict-JSON CrashDump carrying the last-N request
        records from the ring."""
        trace = RequestTrace(capacity=16)
        tr = _tracker([SLOTarget("ttft_ms", 50, 1.0)], trace=trace,
                      on_violation="dump", dump_dir=str(tmp_path),
                      flight_n=3)
        for i in range(5):
            rec = _rec(i, slot=i % 2)
            trace.append(rec)
            tr.observe(rec)  # ttft 12ms >> 1ms: violating
        assert tr.violating_targets() == list(tr.targets)
        assert tr._reg.snapshot()["slo/violating"] == 1.0
        tr(step=42, payload={"serve/tokens_per_sec": 5.0})
        (path,) = tr.dumps
        assert path.endswith("slo_dump_step00000042.json")
        doc = json.loads(open(path).read())  # strict JSON
        assert [r["request_id"] for r in doc["requests"]] == [2, 3, 4]
        assert doc["requests"][0]["ttft_ms"] == pytest.approx(12.0)
        assert doc["config"]["targets"] == ["ttft_ms p50 <= 1ms"]
        assert doc["metrics"]["serve/tokens_per_sec"] == 5.0
        assert tr._reg.snapshot()["slo/violations"] == 1.0

    def test_raise_policy(self, tmp_path):
        tr = _tracker([SLOTarget("ttft_ms", 50, 1.0)],
                      on_violation="raise", dump_dir=str(tmp_path))
        tr.observe(_rec(0))
        with pytest.raises(SLOViolationError, match="ttft_ms p50") as ei:
            tr(step=1, payload={})
        assert ei.value.dump_path and ei.value.dump.requests == []

    def test_skip_policy_never_dumps(self, tmp_path):
        tr = _tracker([SLOTarget("ttft_ms", 50, 1.0)],
                      on_violation="skip", dump_dir=str(tmp_path))
        tr.observe(_rec(0))
        tr(step=1, payload={})
        assert tr.dumps == [] and not list(tmp_path.iterdir())

    def test_consecutive_streak_and_reset(self, tmp_path):
        tr = _tracker([SLOTarget("ttft_ms", 50, 15.0)], window=2,
                      on_violation="dump", dump_dir=str(tmp_path),
                      consecutive=2)
        tr.observe(_rec(0, first=0.020, retire=0.060))  # violating window
        tr.observe(_rec(1, first=0.020, retire=0.060))
        tr(step=1, payload={})
        assert tr.dumps == []  # streak 1 < 2
        tr.observe(_rec(2))  # clean window now
        tr.observe(_rec(3))
        tr(step=2, payload={})
        assert tr.streak == 0 and tr.dumps == []  # reset, no dump
        tr.observe(_rec(4, first=0.020, retire=0.060))
        tr.observe(_rec(5, first=0.020, retire=0.060))
        tr(step=3, payload={})
        assert tr.dumps == []  # fresh streak: 1 < 2 again
        tr(step=4, payload={})  # 2nd consecutive violating report: fires
        assert [p.split("step")[-1] for p in tr.dumps] == ["00000004.json"]

    def test_validation(self):
        with pytest.raises(ValueError, match="at least one"):
            _tracker([])
        with pytest.raises(ValueError, match="on_violation"):
            _tracker([SLOTarget("ttft_ms", 95, 1.0)], on_violation="page")
        with pytest.raises(ValueError, match="window"):
            _tracker([SLOTarget("ttft_ms", 95, 1.0)], window=0)


class TestReporterIntegration:
    def test_slo_hook_through_step_reporter(self, engine, tmp_path):
        """The full wiring, HealthMonitor-style: scheduler feeds tracker,
        StepReporter(hooks=[tracker]) emits the slo/* gauges to sinks
        and the violating report writes the flight dump."""
        buf = io.StringIO()
        reg = MetricsRegistry()
        trace = RequestTrace(capacity=16)
        tracker = SLOTracker(
            [SLOTarget("ttft_ms", 50, 1e-6)],  # impossible: must violate
            registry=reg, trace=trace, on_violation="dump",
            dump_dir=str(tmp_path), flight_n=8)
        sched = SlotScheduler(engine, registry=reg, trace=trace,
                              slo=tracker)
        with StepReporter([JSONLSink(buf)], registry=reg,
                          hooks=[tracker]) as reporter:
            sched.run([Request(prompt=[1 + i], max_new_tokens=2)
                       for i in range(3)])
            reporter.report(0)
        (line,) = [ln for ln in buf.getvalue().splitlines() if ln]
        payload = json.loads(line)["metrics"]
        assert payload["slo/goodput"] == 0.0
        assert payload["slo/violating"] == 1.0
        assert payload["serve/ttft_ms_count"] == 3.0
        (path,) = tracker.dumps
        doc = json.loads(open(path).read())
        assert len(doc["requests"]) == 3
        assert {r["finish_reason"] for r in doc["requests"]} == {"length"}

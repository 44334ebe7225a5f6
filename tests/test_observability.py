"""Telemetry subsystem tests: registry, in-graph accumulators (mesh
aggregation under shard_map), sinks, StepReporter, runtime introspection,
and the amp/DDP/pipeline/optimizer hot-path instrumentation — including
the zero-cost-when-inactive contract asserted on the traced program."""

import io
import json
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import observability as obs
from apex_tpu.observability import ingraph
from jax import shard_map


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_gauge_histogram(self):
        r = obs.MetricsRegistry()
        r.counter("c").inc()
        r.counter("c").inc(2.5)
        r.gauge("g").set(7)
        h = r.histogram("h", buckets=[1.0, 10.0])
        for v in (0.5, 5.0, 50.0, 0.2):
            h.observe(v)
        snap = r.snapshot()
        assert snap["c"] == 3.5
        assert snap["g"] == 7.0
        assert snap["h_count"] == 4.0
        assert snap["h_sum"] == pytest.approx(55.7)
        # Prometheus le contract: cumulative counts, le_inf == count
        assert snap["h_bucket_le_1"] == 2.0
        assert snap["h_bucket_le_10"] == 3.0
        assert snap["h_bucket_le_inf"] == 4.0

    def test_get_or_create_and_kind_conflict(self):
        r = obs.MetricsRegistry()
        assert r.counter("x") is r.counter("x")
        with pytest.raises(TypeError):
            r.gauge("x")

    def test_unset_gauge_skipped_and_reset(self):
        r = obs.MetricsRegistry()
        r.gauge("never_set")
        r.counter("c").inc(5)
        assert "never_set" not in r.snapshot()
        r.reset()
        assert r.snapshot()["c"] == 0.0

    def test_gauge_set_to_nan_is_reported(self):
        """"Unset" is a flag, not a NaN sentinel: a gauge explicitly set
        to NaN (a legitimate health value — NaN abs-max IS the signal)
        must survive into the snapshot."""
        import math

        r = obs.MetricsRegistry()
        g = r.gauge("g")
        assert not g.is_set and math.isnan(g.value)
        g.set(float("nan"))
        assert g.is_set
        assert math.isnan(r.snapshot()["g"])
        g.reset()
        assert not g.is_set and "g" not in r.snapshot()

    def test_default_registry_singleton(self):
        assert obs.get_registry() is obs.get_registry()


# ---------------------------------------------------------------------------
# in-graph accumulators
# ---------------------------------------------------------------------------

class TestInGraph:
    def test_record_is_noop_without_collector(self):
        evaluated = []
        ingraph.record("m", lambda: evaluated.append(1) or 1.0)
        assert not evaluated and not ingraph.recording()

    def test_reap_returns_metrics(self):
        def fn(x):
            ingraph.record("a", x.sum(), reduce="sum")
            ingraph.record("b", lambda: x.max(), reduce="max")
            return x * 2

        out, metrics = jax.jit(ingraph.reap(fn))(jnp.arange(4.0))
        assert np.allclose(out, [0, 2, 4, 6])
        got = metrics.as_floats()
        assert got == {"a": 6.0, "b": 3.0}
        assert metrics.modes["a"] == "sum"

    def test_sum_rerecord_accumulates_others_overwrite(self):
        def fn():
            ingraph.record("s", 1.0, reduce="sum")
            ingraph.record("s", 2.0, reduce="sum")
            ingraph.record("g", 1.0, reduce="mean")
            ingraph.record("g", 5.0, reduce="mean")
            return jnp.zeros(())

        _, m = ingraph.reap(fn)()
        assert m.as_floats() == {"s": 3.0, "g": 5.0}

    def test_mode_conflict_and_bad_inputs(self):
        with ingraph.collecting():
            ingraph.record("m", 1.0, reduce="sum")
            with pytest.raises(ValueError):
                ingraph.record("m", 1.0, reduce="mean")
            with pytest.raises(ValueError):
                ingraph.record("vec", jnp.ones(3))
            with pytest.raises(ValueError):
                ingraph.record("m2", 1.0, reduce="median")

    def test_metrics_is_a_pytree(self):
        m = ingraph.Metrics({"a": jnp.asarray(1.0)}, {"a": "sum"})
        leaves, treedef = jax.tree_util.tree_flatten(m)
        assert len(leaves) == 1
        m2 = jax.tree_util.tree_unflatten(treedef, leaves)
        assert m2.modes == {"a": "sum"} and "a" in m2

    def test_mesh_aggregation_under_shard_map(self):
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))

        def body(x):
            rank = jax.lax.axis_index("data").astype(jnp.float32)
            ingraph.record("r/sum", rank, reduce="sum")
            ingraph.record("r/mean", rank, reduce="mean")
            ingraph.record("r/max", rank, reduce="max")
            ingraph.record("r/min", rank, reduce="min")
            return x

        def inner(x):
            out, metrics = ingraph.reap(body)(x)
            return out, ingraph.aggregate(metrics, "data")

        _, metrics = jax.jit(lambda x: shard_map(
            inner, mesh=mesh, in_specs=P("data"),
            out_specs=(P("data"), P()))(x))(jnp.arange(8.0))
        got = metrics.as_floats()
        assert got == {"r/sum": 6.0, "r/mean": 1.5, "r/max": 3.0,
                       "r/min": 0.0}

    def test_aggregate_identity_without_axes(self):
        _, m = ingraph.reap(lambda: ingraph.record("a", 2.0) or jnp.zeros(()))()
        assert ingraph.aggregate(m, None).as_floats() == {"a": 2.0}


# ---------------------------------------------------------------------------
# zero-cost-when-inactive contract (acceptance criterion)
# ---------------------------------------------------------------------------

class TestZeroCost:
    def _instrumented_step(self):
        from apex_tpu.amp.scaler import DynamicLossScale, all_finite
        from apex_tpu.optimizers import FusedSGD

        scaler = DynamicLossScale()
        opt = FusedSGD(lr=0.1)

        def step(params, opt_state, ls, x):
            grads = jax.grad(lambda p: jnp.sum((x @ p) ** 2))(params)
            finite = all_finite(grads)
            new_ls = scaler.update(ls, finite)
            params, opt_state = opt.step(grads, opt_state, params,
                                         grads_finite=finite)
            return params, opt_state, new_ls

        params = jnp.ones((4, 2))
        opt = FusedSGD(lr=0.1)
        return step, (params, opt.init(params), scaler.init(),
                      jnp.ones((3, 4)))

    def test_no_collector_no_collectives_no_extra_outputs(self):
        """With no collector the instrumented amp+optimizer step must add
        no device collectives, no telemetry math (the grad-norm sqrt), and
        no extra outputs — i.e. no per-step host transfers beyond the
        step's own results."""
        step, args = self._instrumented_step()
        jaxpr = jax.make_jaxpr(step)(*args)
        txt = str(jaxpr)
        for collective in ("psum", "pmean", "pmax", "pmin", "all_reduce"):
            assert collective not in txt
        assert "sqrt" not in txt  # optim/grad_norm's reduction is absent
        n_plain_outputs = len(jax.tree_util.tree_leaves(
            jax.eval_shape(step, *args)))

        reaped = ingraph.reap(step)
        jaxpr_on = jax.make_jaxpr(reaped)(*args)
        assert "sqrt" in str(jaxpr_on)  # grad norm present when collecting
        n_on_outputs = len(jax.tree_util.tree_leaves(
            jax.eval_shape(reaped, *args)))
        assert n_on_outputs > n_plain_outputs

    def test_ddp_allreduce_hlo_unchanged_without_collector(self):
        """The instrumented DDP sync compiles to the same collective count
        as ever when telemetry is off (its metrics are trace-time
        constants, so even with it on, only aggregation adds psums)."""
        from apex_tpu.parallel.distributed import allreduce_grads

        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

        def step(g):
            return shard_map(
                lambda g: allreduce_grads({"w": g, "b": g[0]}, "data"),
                mesh=mesh, in_specs=P("data"),
                out_specs={"w": P("data"), "b": P("data")})(g)

        txt = jax.jit(step).lower(jnp.ones((2, 4))).as_text()
        # one collective per grad leaf, no more (spelling differs between
        # StableHLO and HLO renderings across jax versions)
        assert txt.count("all-reduce") + txt.count("all_reduce") == 2


# ---------------------------------------------------------------------------
# sinks
# ---------------------------------------------------------------------------

class TestSinks:
    def test_jsonl_shape(self):
        buf = io.StringIO()
        sink = obs.JSONLSink(buf)
        sink.emit(3, {"b": 2.0, "a": 1.0})
        line = json.loads(buf.getvalue())
        assert line["step"] == 3
        assert isinstance(line["time"], float)
        assert line["metrics"] == {"a": 1.0, "b": 2.0}
        assert list(line["metrics"]) == ["a", "b"]  # sorted, grep-stable

    def test_jsonl_nonfinite_values_stay_strict_json(self):
        """NaN/inf payload values (legitimate health metrics, NaN-set
        gauges) must serialize as strings, not bare NaN/Infinity
        literals that strict parsers (jq, JSON.parse, Go) reject."""
        buf = io.StringIO()
        obs.JSONLSink(buf).emit(0, {"nan": float("nan"),
                                    "inf": float("inf"),
                                    "ninf": float("-inf"), "ok": 1.5})
        line = json.loads(buf.getvalue(), parse_constant=lambda c:
                          pytest.fail(f"non-standard literal {c}"))
        assert line["metrics"] == {"nan": "NaN", "inf": "Infinity",
                                   "ninf": "-Infinity", "ok": 1.5}

    def test_chrome_counters_nonfinite_safe(self, tmp_path):
        p = tmp_path / "t.json"
        sink = obs.ChromeTraceSink(p)
        sink.emit(0, {"bad": float("inf")})
        sink.close()
        doc = json.loads(p.read_text(), parse_constant=lambda c:
                         pytest.fail(f"non-standard literal {c}"))
        counter = [e for e in doc["traceEvents"] if e["ph"] == "C"][0]
        assert counter["args"]["bad"] == "Infinity"

    def test_jsonl_appends_to_path(self, tmp_path):
        p = tmp_path / "events.jsonl"
        with obs.JSONLSink(p) as sink:
            sink.emit(0, {"x": 1.0})
            sink.emit(1, {"x": 2.0})
        lines = [json.loads(l) for l in p.read_text().splitlines()]
        assert [l["step"] for l in lines] == [0, 1]

    def test_tensorboard_protocol(self):
        calls = []

        class Writer:
            def add_scalar(self, tag, value, step):
                calls.append((tag, value, step))

        obs.TensorBoardSink(Writer()).emit(7, {"b": 2.0, "a": 1.0})
        assert calls == [("a", 1.0, 7), ("b", 2.0, 7)]
        with pytest.raises(TypeError):
            obs.TensorBoardSink(object())

    def test_chrome_trace_spans_and_counters(self, tmp_path):
        p = tmp_path / "trace.json"
        sink = obs.ChromeTraceSink(p, pid=5)
        spans = [obs.Span("fwd", 1.0, 1.5), obs.Span("opt", 1.5, 1.6)]
        sink.emit(2, {"loss": 0.5}, spans)
        sink.close()
        doc = json.loads(p.read_text())
        events = doc["traceEvents"]
        complete = [e for e in events if e["ph"] == "X"]
        assert [e["name"] for e in complete] == ["fwd", "opt"]
        assert complete[0]["dur"] == pytest.approx(0.5e6)
        assert complete[0]["pid"] == 5
        assert complete[0]["args"]["step"] == 2
        counters = [e for e in events if e["ph"] == "C"]
        assert counters and counters[0]["args"] == {"loss": 0.5}

    def test_chrome_trace_interop_spans_plus_perf_gauges(self, tmp_path):
        """Drained spans and in-graph metrics render into ONE Chrome
        trace: span events for the timers, counter events carrying the
        pyprof `perf/*` attribution gauges next to the step metrics —
        well-formed strict JSON."""
        from apex_tpu.pyprof import attribute
        from apex_tpu.utils.timers import Timers

        p = tmp_path / "trace.json"
        timers = Timers()
        reg = obs.MetricsRegistry()
        with obs.StepReporter([obs.ChromeTraceSink(p, pid=3)],
                              registry=reg, timers=timers,
                              capture_spans=True) as rep:
            report = attribute(
                lambda x, w: jnp.sum(x @ w), 0.004,
                args=(jnp.ones((8, 8)), jnp.ones((8, 8))))
            rep.attach_attribution(report)
            with timers("fwd")():
                time.sleep(0.001)
            _, metrics = ingraph.reap(
                lambda: ingraph.record("m", 2.5) or jnp.zeros(()))()
            rep.report(0, metrics=metrics)
        doc = json.loads(p.read_text(), parse_constant=lambda c:
                         pytest.fail(f"non-standard literal {c}"))
        events = doc["traceEvents"]
        spans = [e for e in events if e["ph"] == "X"]
        assert [e["name"] for e in spans] == ["fwd"]
        counters = {k: v for e in events if e["ph"] == "C"
                    for k, v in e["args"].items()}
        assert counters["m"] == 2.5
        assert counters["perf/modeled_step_ms"] == pytest.approx(
            report.modeled_step_ms)
        assert counters["perf/comm_exposed_ms"] == 0.0


# ---------------------------------------------------------------------------
# StepReporter + timer spans
# ---------------------------------------------------------------------------

class TestStepReporter:
    def test_merges_ingraph_registry_timers_extra(self):
        from apex_tpu.utils.timers import Timers

        reg = obs.MetricsRegistry()
        reg.counter("host/c").inc(4)
        timers = Timers()
        timers("fwd").start()
        time.sleep(0.002)
        timers("fwd").stop()
        buf = io.StringIO()
        rep = obs.StepReporter([obs.JSONLSink(buf)], registry=reg,
                               timers=timers)
        _, metrics = ingraph.reap(
            lambda: ingraph.record("m", 1.5) or jnp.zeros(()))()
        payload = rep.report(0, metrics=metrics, extra={"loss": 2.0})
        assert payload["m"] == 1.5
        assert payload["host/c"] == 4.0
        assert payload["loss"] == 2.0
        assert payload["time/fwd_ms"] >= 2.0
        assert json.loads(buf.getvalue())["metrics"]["m"] == 1.5
        # reset_timers=True drained the timer
        assert timers("fwd").elapsed(reset=False) == 0.0

    def test_interval_gating(self):
        emitted = []

        class Spy(obs.JSONLSink):
            def __init__(self):
                pass

            def emit(self, step, metrics, spans=()):
                emitted.append(step)

            def close(self):
                pass

        rep = obs.StepReporter([Spy()], registry=obs.MetricsRegistry(),
                               interval=3)
        for s in range(7):
            rep.report(s)
        assert emitted == [0, 3, 6]

    def test_timer_spans_reach_chrome_sink(self, tmp_path):
        from apex_tpu.utils.timers import Timers

        p = tmp_path / "t.json"
        timers = Timers()
        with obs.StepReporter([obs.ChromeTraceSink(p)],
                              registry=obs.MetricsRegistry(),
                              timers=timers, capture_spans=True) as rep:
            with timers("step")():
                time.sleep(0.001)
            rep.report(0)
        assert not obs.spans_enabled()  # close() restored the default
        events = json.loads(p.read_text())["traceEvents"]
        spans = [e for e in events if e["ph"] == "X"]
        assert [e["name"] for e in spans] == ["step"]

    def test_mfu_gauge_from_flops_budget(self):
        """With a flops budget attached, consecutive reports carry a
        perf/mfu gauge computed from the wall time between them."""
        emitted = []

        class Spy(obs.JSONLSink):
            def __init__(self):
                pass

            def emit(self, step, metrics, spans=()):
                emitted.append(dict(metrics))

            def close(self):
                pass

        rep = obs.StepReporter([Spy()], registry=obs.MetricsRegistry())
        with pytest.raises(ValueError):
            rep.attach_flops_budget(1e6, peak=0.0)  # fail at config time
        with pytest.raises(ValueError):
            rep.attach_flops_budget(-1.0)
        assert rep.attach_flops_budget(1e6, peak=1e9) is rep
        rep.report(0)
        assert "perf/mfu" not in emitted[0]  # no prior report to diff
        time.sleep(0.005)
        rep.report(2)
        # 2 steps x 1e6 flops over >= 5ms against a 1e9 peak
        assert 0.0 < emitted[1]["perf/mfu"] <= 2e6 / 0.005 / 1e9
        # the gauge also lands in the registry for later snapshots
        assert rep.registry.snapshot()["perf/mfu"] == emitted[1]["perf/mfu"]

    def test_memory_budget_gauges(self):
        """attach_memory_budget sets the mem/* gauge family — from a
        budget dict or straight from a compiled executable — and a
        None-budget backend leaves the gauges unset (no fabricated
        zeros)."""
        rep = obs.StepReporter([], registry=obs.MetricsRegistry())
        budget = {"argument_bytes": 100, "output_bytes": 10,
                  "temp_bytes": 50, "alias_bytes": 0,
                  "generated_code_bytes": 1, "host_temp_bytes": 0,
                  "peak_hbm_bytes": 161}
        assert rep.attach_memory_budget(budget) is rep
        snap = rep.registry.snapshot()
        assert snap["mem/peak_hbm_bytes"] == 161.0
        assert snap["mem/temp_bytes"] == 50.0
        assert snap["mem/argument_bytes"] == 100.0
        assert snap["mem/output_bytes"] == 10.0
        assert snap["mem/host_temp_bytes"] == 0.0

        # straight from a compiled executable (skip silently if the
        # backend reports no analysis — then nothing may be set)
        rep2 = obs.StepReporter([], registry=obs.MetricsRegistry())
        compiled = jax.jit(lambda x: jnp.sum(x * x)).lower(
            jnp.ones((32, 32))).compile()
        rep2.attach_memory_budget(compiled)
        snap2 = rep2.registry.snapshot()
        if obs.memory_budget(compiled) is not None:
            assert snap2["mem/peak_hbm_bytes"] > 0
        # an analysis-less object must leave the family unset
        rep3 = obs.StepReporter([], registry=obs.MetricsRegistry())
        rep3.attach_memory_budget(object())
        assert not any(k.startswith("mem/")
                       for k in rep3.registry.snapshot())

    def test_null_reporter_default(self):
        obs.detach_reporter()
        rep = obs.get_reporter()
        assert not rep
        assert rep.report(0, extra={"x": 1}) is None
        real = obs.attach_reporter(
            obs.StepReporter([], registry=obs.MetricsRegistry()))
        try:
            assert obs.get_reporter() is real
        finally:
            obs.detach_reporter()
        assert not obs.get_reporter()


# ---------------------------------------------------------------------------
# trace span buffer under concurrency
# ---------------------------------------------------------------------------

class TestTraceConcurrency:
    def test_concurrent_record_and_drain_loses_nothing(self):
        """Producer threads hammer record_span while a drainer races
        drain_spans: every span must come out exactly once (the _SPANS
        buffer swap is lock-protected on both sides)."""
        import threading

        from apex_tpu.observability import trace

        n_producers, n_spans = 4, 300
        drained = []
        stop = threading.Event()

        def produce(k):
            for i in range(n_spans):
                trace.record_span(f"p{k}-{i}", float(i), float(i) + 1.0)

        def drain():
            while not stop.is_set():
                drained.extend(trace.drain_spans())

        trace.enable_spans()
        try:
            threads = [threading.Thread(target=produce, args=(k,))
                       for k in range(n_producers)]
            drainer = threading.Thread(target=drain)
            drainer.start()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            stop.set()
            drainer.join()
            drained.extend(trace.drain_spans())
        finally:
            trace.disable_spans()
        names = [s.name for s in drained]
        assert len(names) == n_producers * n_spans
        assert len(set(names)) == len(names)  # no duplicates either

    def test_disable_drops_undrained_spans(self):
        from apex_tpu.observability import trace

        trace.enable_spans()
        trace.record_span("stale", 0.0, 1.0)
        trace.disable_spans()
        trace.enable_spans()
        try:
            assert trace.drain_spans() == []
        finally:
            trace.disable_spans()


# ---------------------------------------------------------------------------
# costs: peak-flops table + MFU math (shared with bench.py)
# ---------------------------------------------------------------------------

class TestCosts:
    def test_peak_flops_table_and_fallback(self):
        class Fake:
            def __init__(self, kind):
                self.device_kind = kind

        assert obs.peak_flops(Fake("TPU v4 something")) == 275e12
        assert obs.peak_flops(Fake("TPU v5e")) == 197e12
        from apex_tpu.observability.costs import DEFAULT_PEAK_FLOPS
        assert obs.peak_flops(Fake("cpu")) == DEFAULT_PEAK_FLOPS
        assert obs.peak_flops() == DEFAULT_PEAK_FLOPS  # CPU test host

    @pytest.mark.parametrize("kind,platform", [
        ("TPU v9 hypothetical", "tpu"),   # a TPU the table has not learned
        ("TPU v9 hypothetical", None),    # kind alone is enough to refuse
        ("NVIDIA H100", "gpu"),
    ])
    def test_unknown_accelerator_kind_raises(self, kind, platform):
        """The v5e-class default is the CPU backend's stand-in only: a
        utilization against an ASSUMED peak would read as a measurement,
        so an accelerator the tables do not know is an error in both
        lookups."""
        from apex_tpu.observability.costs import device_spec

        class Fake:
            device_kind = kind
        if platform is not None:
            Fake.platform = platform
        with pytest.raises(ValueError, match="no peak numbers"):
            obs.peak_flops(Fake())
        with pytest.raises(ValueError, match="no peak numbers"):
            device_spec(Fake())

    def test_flops_budget_from_compiled(self):
        compiled = jax.jit(lambda x: x @ x).lower(
            jnp.ones((8, 8))).compile()
        budget = obs.flops_budget(compiled)
        # the CPU backend reports a real flop count for a matmul; a
        # backend without cost analysis must yield None, not raise
        assert budget is None or budget > 0
        assert obs.flops_budget(object()) is None

    def test_mfu_math(self):
        assert obs.mfu(10.0, 2.0, peak=1.0) == 5.0
        # zero/negative step time returns NaN (gauge stays unset) rather
        # than raising mid-report — the first-report wall delta can be
        # ~0 on a fast host (regression: tests/test_pyprof.py pins the
        # reporter-level behavior)
        import math
        assert math.isnan(obs.mfu(1.0, 0.0, peak=1.0))
        assert math.isnan(obs.mfu(1.0, -1.0, peak=1.0))
        assert math.isnan(obs.mfu(1.0, 1.0, peak=0.0))

    def test_bench_imports_from_costs(self):
        """bench.py must not regrow its own table — one source of truth."""
        import ast
        src = ast.parse(open("bench.py").read())
        assigned = {t.id for node in ast.walk(src)
                    if isinstance(node, ast.Assign)
                    for t in node.targets if isinstance(t, ast.Name)}
        assert "_PEAK_BF16" not in assigned
        imports = [n for node in ast.walk(src)
                   if isinstance(node, ast.ImportFrom)
                   and node.module == "apex_tpu.observability.costs"
                   for n in node.names]
        assert {a.name for a in imports} >= {"flops_budget", "peak_flops",
                                             "memory_budget"}

    def test_memory_budget_from_compiled(self):
        """memory_analysis() extraction: real bytes on backends that report
        (the CPU backend does), None — never a raise — otherwise."""
        compiled = jax.jit(
            lambda x, w: jnp.sum(jnp.tanh(x @ w) @ w)).lower(
            jnp.ones((64, 64)), jnp.ones((64, 64))).compile()
        budget = obs.memory_budget(compiled)
        assert obs.memory_budget(object()) is None
        if budget is None:  # backend without memory analysis
            return
        for key in ("argument_bytes", "output_bytes", "temp_bytes",
                    "alias_bytes", "generated_code_bytes",
                    "host_temp_bytes", "peak_hbm_bytes"):
            assert key in budget and budget[key] >= 0, key
        # two 64x64 fp32 args, and the high-water covers them
        assert budget["argument_bytes"] == 2 * 64 * 64 * 4
        assert budget["peak_hbm_bytes"] >= budget["argument_bytes"]


# ---------------------------------------------------------------------------
# runtime introspection
# ---------------------------------------------------------------------------

class TestRuntime:
    def test_compile_listener_counts_fresh_compile(self):
        reg = obs.MetricsRegistry()
        assert obs.install_compile_listeners(reg) is reg
        obs.install_compile_listeners(reg)  # idempotent: no double count
        before = reg.counter("jax/compiles").value
        salt = np.random.default_rng().integers(1 << 30)
        jax.jit(lambda x: x * float(salt))(jnp.ones(3)).block_until_ready()
        after = reg.counter("jax/compiles").value
        assert after == before + 1
        assert reg.counter("jax/traces").value >= after
        snap = reg.snapshot()
        assert snap["jax/compile_seconds_count"] == after

    def test_uninstall_and_reinstall(self):
        """Listener lifecycles are reversible: an uninstalled registry's
        counters stop moving, a reinstalled one counts again — repeated
        StepReporter-style lifecycles cannot double-count."""
        def fresh_compile():
            salt = np.random.default_rng().integers(1 << 30)
            jax.jit(lambda x: x + float(salt))(
                jnp.ones(3)).block_until_ready()

        reg = obs.MetricsRegistry()
        obs.install_compile_listeners(reg)
        fresh_compile()
        counted = reg.counter("jax/compiles").value
        assert counted >= 1
        assert obs.uninstall_compile_listeners(reg)
        assert not obs.uninstall_compile_listeners(reg)  # already gone
        fresh_compile()
        assert reg.counter("jax/compiles").value == counted  # frozen
        obs.install_compile_listeners(reg)
        fresh_compile()
        assert reg.counter("jax/compiles").value == counted + 1

    def test_reset_detaches_everything(self):
        regs = [obs.MetricsRegistry(), obs.MetricsRegistry()]
        for r in regs:
            obs.install_compile_listeners(r)
        obs.reset_compile_listeners()
        salt = np.random.default_rng().integers(1 << 30)
        jax.jit(lambda x: x - float(salt))(jnp.ones(3)).block_until_ready()
        for r in regs:
            assert r.counter("jax/compiles").value == 0

    def test_memory_stats_sampler(self):
        reg = obs.MetricsRegistry()
        out = obs.sample_memory_stats(reg)
        # CPU backends expose no allocator stats; on TPU/GPU each device
        # contributes bytes_in_use
        for name, value in out.items():
            assert name.startswith("memory/")
            assert value >= 0
            assert reg.snapshot()[name] == value


# ---------------------------------------------------------------------------
# hot-path instrumentation
# ---------------------------------------------------------------------------

class TestHotPaths:
    def test_amp_scaler_metrics_on_overflow(self):
        from apex_tpu.amp.scaler import DynamicLossScale

        scaler = DynamicLossScale(init_scale=16.0)

        def update(ls, finite):
            return scaler.update(ls, finite)

        reaped = jax.jit(ingraph.reap(update))
        _, m = reaped(scaler.init(), jnp.asarray(False))
        got = m.as_floats()
        assert got["amp/loss_scale"] == 8.0  # halved on overflow
        assert got["amp/overflow_count"] == 1.0
        assert got["amp/skipped_steps"] == 1.0
        _, m = reaped(scaler.init(), jnp.asarray(True))
        got = m.as_floats()
        assert got["amp/loss_scale"] == 16.0
        assert got["amp/overflow_count"] == 0.0

    def test_static_scaler_also_reports(self):
        from apex_tpu.amp.scaler import StaticLossScale

        scaler = StaticLossScale(scale=4.0)
        _, m = ingraph.reap(scaler.update)(scaler.init(),
                                           jnp.asarray(False))
        got = m.as_floats()
        assert got["amp/loss_scale"] == 4.0
        assert got["amp/skipped_steps"] == 1.0

    def test_ddp_allreduce_bytes_mesh_aggregated(self):
        from apex_tpu.parallel.distributed import allreduce_grads

        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        grads = {"w": jnp.ones((2, 8, 4)), "b": jnp.ones((2, 4))}
        per_rank = 8 * 4 * 4 + 4 * 4  # f32 leaf bytes on one rank

        def inner(g):
            out, m = ingraph.reap(
                lambda g: allreduce_grads(g, "data"))(g)
            return out, ingraph.aggregate(m, "data")

        _, m = jax.jit(lambda g: shard_map(
            inner, mesh=mesh,
            in_specs=({"w": P("data"), "b": P("data")},),
            out_specs=({"w": P("data"), "b": P("data")}, P()))(g))(grads)
        got = m.as_floats()
        assert got["ddp/allreduce_bytes"] == 2 * per_rank  # psum over mesh
        assert got["ddp/buckets"] == 2.0

    def test_ddp_fp32_upcast_counts_fp32_bytes(self):
        from apex_tpu.parallel.distributed import allreduce_grads

        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        g16 = jnp.ones((2, 8), jnp.bfloat16)

        def inner(g):
            out, m = ingraph.reap(lambda g: allreduce_grads(
                g, "data", allreduce_always_fp32=True))(g)
            return out, ingraph.aggregate(m, "data")

        _, m = jax.jit(lambda g: shard_map(
            inner, mesh=mesh, in_specs=P("data"),
            out_specs=(P("data"), P()))(g))(g16)
        assert m.as_floats()["ddp/allreduce_bytes"] == 2 * 8 * 4

    def test_optimizer_grad_norm(self):
        from apex_tpu.optimizers import FusedSGD

        opt = FusedSGD(lr=0.0)  # lr 0: params unchanged, norm still real
        params = {"a": jnp.ones(3), "b": jnp.zeros(2)}
        grads = {"a": jnp.full(3, 2.0), "b": jnp.zeros(2)}

        def step(g, s, p):
            return opt.step(g, s, p)

        _, m = jax.jit(ingraph.reap(step))(grads, opt.init(params), params)
        assert m.as_floats()["optim/grad_norm"] == pytest.approx(
            float(np.sqrt(12.0)))

    def test_pipeline_no_pipelining_reports_zero_bubble(self):
        from apex_tpu.transformer.pipeline_parallel.schedules import (
            forward_backward_no_pipelining)

        batch = jnp.ones((4, 2, 3))
        params = {"w": jnp.ones((3,))}

        def fwd(p, mb):
            return jnp.mean(mb * p["w"])

        def run(params):
            return forward_backward_no_pipelining(fwd, batch, params)

        _, m = jax.jit(ingraph.reap(run))(params)
        got = m.as_floats()
        assert got["pipeline/bubble_fraction"] == 0.0
        assert got["pipeline/num_microbatches"] == 4.0
        assert got["pipeline/ticks"] == 4.0

    def test_pipeline_1f1b_bubble_fraction(self):
        from apex_tpu.transformer.pipeline_parallel.schedules import (
            forward_backward_pipelining_without_interleaving)

        mesh = Mesh(np.array(jax.devices()[:2]), ("pipe",))
        pp, M, D = 2, 4, 4
        ws = jnp.ones((pp, D, D)) * 0.1
        micro = jnp.ones((M, 2, D))

        def stage(p, x, s):
            return jnp.tanh(x @ p["w"])

        def inner(ws):
            def body(ws):
                return forward_backward_pipelining_without_interleaving(
                    stage, micro, {"w": ws[0]},
                    loss_fn=lambda y, m: jnp.mean(y ** 2))
            out, m = ingraph.reap(body)(ws)
            return out, ingraph.aggregate(m, "pipe")

        (_, _), m = jax.jit(lambda w: shard_map(
            inner, mesh=mesh, in_specs=(P("pipe"),),
            out_specs=((P(), {"w": P("pipe")}), P()))(w))(ws)
        got = m.as_floats()
        # fwd+bwd 1F1B scan: T = M + 2L - 1 = 7 ticks, M useful -> 3/7
        assert got["pipeline/ticks"] == 7.0
        assert got["pipeline/bubble_fraction"] == pytest.approx(3.0 / 7.0)


# ---------------------------------------------------------------------------
# the acceptance toy run: 3 steps, full stream, mesh-aggregated
# ---------------------------------------------------------------------------

def test_three_step_toy_run_emits_full_stream(tmp_path):
    """amp + DDP + pipelined schedule + fused optimizer on a pipe x data
    CPU mesh for 3 steps: the JSONL stream must carry the whole documented
    metric surface with per-rank values psum-aggregated across the mesh."""
    from apex_tpu.amp.scaler import DynamicLossScale, all_finite
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.optimizers.fused_sgd import SGDState
    from apex_tpu.parallel.distributed import allreduce_grads
    from apex_tpu.transformer.pipeline_parallel.schedules import (
        forward_backward_pipelining_without_interleaving)

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("pipe", "data"))
    pp, M, mb, D = 2, 4, 2, 8
    rng = np.random.RandomState(0)
    ws = jnp.asarray(rng.randn(pp, D, D) * 0.3, jnp.float32)
    micro = jnp.asarray(rng.randn(M, 2 * mb, D), jnp.float32)
    scaler = DynamicLossScale(init_scale=2.0 ** 4, growth_interval=2)
    opt = FusedSGD(lr=1e-2, momentum=0.9)
    opt_state = opt.init(ws)
    ls = scaler.init()

    def stage(p, x, s):
        return jnp.tanh(x @ p["w"])

    def body(ws, opt_state, ls, micro):
        loss, grads = forward_backward_pipelining_without_interleaving(
            stage, micro, {"w": ws[0]},
            loss_fn=lambda y, m: jnp.mean(y ** 2),
            grad_scale=ls.loss_scale)
        grads = allreduce_grads(grads["w"][None], "data")
        finite = all_finite(grads, axis_names=("pipe",))
        new_ls = scaler.update(ls, finite)
        new_w, new_s = opt.step(grads, opt_state, ws, grads_finite=finite)
        return jax.lax.pmean(loss, "data"), new_w, new_s, new_ls

    def inner(*args):
        out, metrics = ingraph.reap(body)(*args)
        return out + (ingraph.aggregate(metrics, ("pipe", "data")),)

    ospec = SGDState(step=P(), momentum_buf=P("pipe"))
    step = jax.jit(lambda w, s, l, m: shard_map(
        inner, mesh=mesh,
        in_specs=(P("pipe"), ospec, P(), P(None, "data")),
        out_specs=(P(), P("pipe"), ospec, P(), P()))(w, s, l, m))

    path = tmp_path / "telemetry.jsonl"
    with obs.StepReporter([obs.JSONLSink(path)],
                          registry=obs.MetricsRegistry()) as rep:
        for i in range(3):
            loss, ws, opt_state, ls, metrics = step(ws, opt_state, ls,
                                                    micro)
            rep.report(i, metrics=metrics, extra={"loss": float(loss)})

    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [l["step"] for l in lines] == [0, 1, 2]
    for line in lines:
        m = line["metrics"]
        for key in ("amp/loss_scale", "amp/overflow_count",
                    "amp/skipped_steps", "ddp/allreduce_bytes",
                    "ddp/buckets", "optim/grad_norm",
                    "pipeline/bubble_fraction", "pipeline/ticks",
                    "pipeline/num_microbatches", "loss"):
            assert key in m, key
    last = lines[-1]["metrics"]
    # psum-aggregation across the 4-device mesh: each rank contributes its
    # (1, D, D) f32 grad leaf per sync
    assert last["ddp/allreduce_bytes"] == 4 * D * D * 4
    # growth_interval=2, 3 clean steps -> one doubling of 2**4
    assert last["amp/loss_scale"] == 32.0
    assert last["pipeline/bubble_fraction"] == pytest.approx(3.0 / 7.0)
    assert last["optim/grad_norm"] > 0.0


def test_hybrid_trainer_step_with_metrics():
    """GPTHybridTrainer.train_step_with_metrics must produce the same loss
    as train_step plus the full mesh-aggregated telemetry surface."""
    from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                                 ParallelConfig, TrainConfig)
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    tp, pp, dp = 2, 2, 2
    M, mb, seq = 4, 2, 8
    cfg = TrainConfig(
        model=ModelConfig(name="gpt", vocab_size=64, hidden_size=32,
                          num_layers=2 * pp, num_attention_heads=4,
                          max_position_embeddings=seq),
        parallel=ParallelConfig(tensor_model_parallel_size=tp,
                                pipeline_model_parallel_size=pp),
        batch=BatchConfig(global_batch_size=M * mb * dp,
                          micro_batch_size=mb),
        optimizer=OptimizerConfig(name="adam", lr=1e-2, weight_decay=0.0),
        opt_level="O0")
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, (M, dp * mb, seq)))
    targets = jnp.asarray(rng.randint(0, 64, (M, dp * mb, seq)))
    mesh = cfg.initialize_mesh(devices=jax.devices())
    try:
        trainer = GPTHybridTrainer(cfg, mesh)
        state = trainer.init_state(jax.random.PRNGKey(0))
        loss, *_ = jax.jit(trainer.train_step)(*state, tokens, targets)
        loss_m, _, _, _, _, metrics = jax.jit(
            trainer.train_step_with_metrics)(*state, tokens, targets)
    finally:
        parallel_state.destroy_model_parallel()
    assert float(loss) == pytest.approx(float(loss_m), abs=1e-6)
    got = metrics.as_floats()
    for key in ("amp/loss_scale", "amp/overflow_count", "amp/skipped_steps",
                "ddp/allreduce_bytes", "ddp/buckets", "optim/grad_norm",
                "pipeline/bubble_fraction", "pipeline/ticks"):
        assert key in got, key
    assert got["ddp/allreduce_bytes"] > 0
    # 1F1B over pp=2, M=4: T = 7 ticks, bubble 3/7
    assert got["pipeline/bubble_fraction"] == pytest.approx(3.0 / 7.0)


# ---------------------------------------------------------------------------
# static contract checks
# ---------------------------------------------------------------------------
# The six per-script test classes that used to live here (annotations,
# collectives, metrics-doc, remat-names, elastic-exits, bench-configs)
# moved to tests/test_analysis.py as ONE parametrized planted-violation
# suite over the unified engine (apex_tpu.analysis, PR 11). What remains
# here is the back-compat contract: the scripts/ shims still expose the
# historical check(repo) -> (ok, lines) surface and pass on this tree.

_SHIM_SCRIPTS = ("check_annotations", "check_collectives",
                 "check_metrics_doc", "check_remat_names",
                 "check_elastic_exits", "check_bench_configs")


@pytest.mark.parametrize("script", _SHIM_SCRIPTS)
def test_check_script_shim_passes_on_this_tree(script):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        script, f"scripts/{script}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ok, lines = mod.check()
    assert ok, "\n".join(lines)
    assert lines  # the report still enumerates what was checked
    assert callable(mod.main)

"""apex_tpu.observability — structured training telemetry.

The reference exposes runtime behavior only through ad-hoc prints (amp's
``maybe_print``, ``reference:apex/amp/_amp_state.py:39-51``; Megatron
``_Timers.log``) and the deprecated pyprof pipeline. This package is the
structured replacement: one stream that answers "what did this step spend,
where, on which rank" without a trace capture.

Composable modules, each zero-cost when unused:

- :mod:`~apex_tpu.observability.registry` — host-side counters, gauges and
  fixed-bucket histograms (``Metric.observe()``), grouped in a
  :class:`MetricsRegistry`;
- :mod:`~apex_tpu.observability.ingraph` — the in-graph accumulator: traced
  code calls :func:`record`, a reaping wrapper returns the recorded scalars
  as a pytree of device values, and :func:`aggregate` psums them across the
  mesh at report time (no host round-trips inside the step);
- :mod:`~apex_tpu.observability.report` / ``sinks`` — a
  :class:`StepReporter` snapshotting registry + ``Timers`` + in-graph
  metrics each step into pluggable sinks (JSONL event log, TensorBoard
  ``add_scalar`` writers, Chrome-trace span export);
- :mod:`~apex_tpu.observability.trace` — :func:`span`, the one span
  function of the program's host code (``apex:`` annotations on the
  profiler's clock; the table :data:`SPANS`), and the in-memory span
  buffer behind the Chrome-trace export;
- :mod:`~apex_tpu.observability.runtime` — compile/recompile counters via
  ``jax.monitoring`` listeners and a ``memory_stats()`` gauge sampler, so
  recompilation storms and HBM growth land in the same stream;
- :mod:`~apex_tpu.observability.health` — the numerics watchdog: per-leaf
  NaN/overflow attribution (``health/*``), replica-agreement checks, and
  the :class:`HealthConfig` policy whose :class:`HealthMonitor` reporter
  hook raises or writes a structured :class:`CrashDump` on a non-finite
  step;
- :mod:`~apex_tpu.observability.costs` — the peak-flops table and MFU
  math shared by ``bench.py`` and the reporter's ``perf/mfu`` gauge;
- :mod:`~apex_tpu.observability.reqtrace` /
  :mod:`~apex_tpu.observability.slo` — the serving-side request
  lifecycle: per-request span records with TTFT/TPOT/queue-wait/e2e
  latencies, a bounded flight-recorder ring with a per-slot-swimlane
  Chrome-trace export, and :class:`SLOTracker` — declarative latency
  targets, rolling goodput/burn-rate gauges (``slo/*``), and a
  flight-recorder :class:`CrashDump` on violation;
- :mod:`~apex_tpu.observability.perfwatch` — the performance
  observatory: the append-only ``BENCH_HISTORY.jsonl`` bench history
  (:class:`BenchHistory`, full-precision ``raw_value`` + git/host
  provenance, ``BENCH_r*.json`` importer), the rolling-median+MAD
  :class:`RegressionDetector` with unit-inferred direction,
  :class:`AttributionDiff` region diffs naming the suspect region, and
  measured/modeled cost-model drift (``perf/model_drift`` gauges +
  shift alerts); CLI: ``python -m apex_tpu.perfwatch``;
- :mod:`~apex_tpu.observability.fleet` — the cross-rank merge layer:
  rank-side registry snapshots (:class:`FleetPublisher`, atomic JSON),
  the supervisor-side :class:`FleetAggregator` (counters sum, gauges
  min/max/mean + spread, histogram buckets add) with the ``fleet/*``
  straggler family, :class:`PostmortemReport` gang forensics, and a
  stdlib :class:`MetricsServer` serving ``/metrics`` (Prometheus text
  via ``render_prometheus``) + ``/fleet`` (merged JSON).

Hot paths in the library are pre-instrumented (``amp/*``, ``ddp/*``,
``pipeline/*``, ``optim/*``, ``health/*`` — see ``docs/OBSERVABILITY.md``);
with no collector active every instrumentation point is a module-level
no-op that adds nothing to the traced program.
"""

from apex_tpu.observability.registry import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, get_registry, log_buckets)
from apex_tpu.observability.ingraph import (  # noqa: F401
    Metrics, aggregate, collecting, reap, record, recording)
from apex_tpu.observability.trace import (  # noqa: F401
    SPANS, Span, chrome_trace_events, drain_spans, epoch_offset,
    merge_chrome_traces, span, span_recording, spans_enabled)
from apex_tpu.observability.sinks import (  # noqa: F401
    ChromeTraceSink, JSONLSink, TensorBoardSink)
from apex_tpu.observability.report import (  # noqa: F401
    NullReporter, StepReporter, attach_reporter, detach_reporter,
    get_reporter)
from apex_tpu.observability.runtime import (  # noqa: F401
    install_compile_listeners, reset_compile_listeners,
    sample_memory_stats, uninstall_compile_listeners)
from apex_tpu.observability.health import (  # noqa: F401
    CrashDump, HealthConfig, HealthMonitor, NonFiniteError, TreeStats,
    check_replica_agreement, decode_attribution, tensor_stats)
from apex_tpu.observability.costs import (  # noqa: F401
    flops_budget, memory_budget, mfu, peak_flops)
from apex_tpu.observability.reqtrace import (  # noqa: F401
    LATENCY_BUCKETS_MS, RequestRecord, RequestTrace, chrome_request_trace)
from apex_tpu.observability.slo import (  # noqa: F401
    SLOTarget, SLOTracker, SLOViolationError)
from apex_tpu.observability.fleet import (  # noqa: F401
    FleetAggregator, FleetPublisher, MetricsServer, PostmortemReport,
    merge_registry_dicts)
from apex_tpu.observability.perfwatch import (  # noqa: F401
    AttributionDiff, BenchHistory, DriftShift, Regression,
    RegressionDetector, detect_drift_shifts, drift_series, publish_drift,
    unit_direction)

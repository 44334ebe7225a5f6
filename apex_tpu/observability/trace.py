"""Wall-clock span capture + Chrome-trace (Perfetto-loadable) conversion.

:class:`~apex_tpu.utils.timers.Timer` records *elapsed totals*; the trace
viewers want *spans*. When span recording is enabled, every ``Timer.stop``
pushes ``(name, t0, t1)`` here via a hook installed into
``apex_tpu.utils.timers`` (a plain module-global check — one ``is None``
test per stop when disabled, and no import cycle: this module imports
nothing from the rest of the library). The
:class:`~apex_tpu.observability.sinks.ChromeTraceSink` drains the buffer
each report and writes the standard ``traceEvents`` JSON, which loads in
``chrome://tracing`` / Perfetto next to a ``jax.profiler.trace`` capture —
host-side step phases and device-side ops in the same timeline workflow.

**Program spans.** :func:`span` is the one span function of the program's
own host code (the serving scheduler and engines): it opens a
``jax.profiler.TraceAnnotation`` named ``"apex:" + name``, so inside a
profiler session (:func:`~apex_tpu.utils.timers.profile_trace`) the span
lands in the profiler's ``.xplane.pb`` on plane ``/host:CPU``, line
``python3``, ON THE CLOCK OF THE DEVICE PLANES — every idle gap of the
device can be laid against what the host was doing. Keywords (``request_id``,
``slot``, ``step``) arrive as stats of the event. With no session the
annotation costs what a ``nullcontext`` costs (under half a microsecond,
no clock read, no lock). Under :func:`span_recording` the same call also
appends to the in-memory buffer, with the enclosing span as ``parent``:
that view sees set-up (the profiler's window opens later) and needs no
profiler. :data:`SPANS` is the one table of names.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["Span", "SPANS", "SPAN_PREFIX", "span", "spans_enabled",
           "enable_spans", "disable_spans", "record_span", "drain_spans",
           "span_recording", "chrome_trace_events", "epoch_offset",
           "trace_metadata", "merge_chrome_traces"]

SPAN_PREFIX = "apex:"

# Every name span() may be given -> what the interval covers. Indentation
# in the comments is nesting. PERF.md section 3 and docs/OBSERVABILITY.md
# copy this table and name the metric that reads each span.
SPANS: Dict[str, str] = {
    "sched.submit": "SlotScheduler.submit: validation, shedding, the "
                    "RequestRecord [request_id]",
    "sched.step": "one SlotScheduler.step() [step: decode steps run "
                  "before it]",
    # inside sched.step
    "sched.expire": "_expire_queued: the walk over queued deadlines",
    "sched.admit": "one request past the admission checks: slot taken, "
                   "engine.prefill, _record of its first token "
                   "[request_id, slot, prompt_len]",
    "sched.harvest": "from the stamp after the engine call to the last "
                     "retirement of the step",
    "sched.gauges": "the serve/* counters and gauges set at the end of "
                    "a step",
    # inside sched.admit
    "engine.prefill": "prefill() of the engine [slot]",
    "prefill.plan": "allocator.lookup/admit, pad_prompt, the "
                    "_host() marshalling, _next_key()",
    "prefill.dispatch": "the call of the prefill program (returns before "
                        "the device is done) [on an engine with several "
                        "prefill buckets: bucket, its positions; tokens, "
                        "the prompt's own, the rest padding]",
    "prefill.wait": "int(tok): the host blocks until the device has the "
                    "token",
    "prefill.index": "allocator.register_prefix",
    # inside sched.step (and inside engine.prefill on a prefix hit)
    "engine.decode": "decode() of the engine [active: slots stepped]",
    "decode.plan": "prepare_step, append_targets, the _host() "
                   "marshalling, _next_key()",
    "decode.dispatch": "the call of decode_compiled",
    "decode.advance": "allocator.advance, while the device runs",
    "decode.wait": "np.asarray(toks), and last_finite on a quarantine "
                   "engine: the host blocks until the device is done",
    "engine.verify": "verify() of the engine [active]",
    "verify.plan": "prepare_verify, verify_targets, marshalling, "
                   "_next_key()",
    "verify.dispatch": "the call of verify_compiled",
    "verify.wait": "np.asarray of tokens and counts (and last_finite)",
    "verify.advance": "allocator.advance_counts",
    # inside sched.harvest (or sched.step, or a cancel/drain)
    "engine.release": "release_slot: allocator.release + the "
                      "release_compiled dispatch [slot]",
    # construction
    "engine.build": "ServingEngine.__init__ after the "
                    "argument checks",
    "compile.image": "trace + lower + compile of the cast program that "
                     "makes the weights' serving image (only where the "
                     "image is not the handed tree)",
    "compile.prefill": "trace + lower + compile of the prefill program",
    "compile.decode": "trace + lower + compile of the decode program",
    "compile.verify": "trace + lower + compile of the verify program",
    "compile.release": "trace + lower + compile of the release program",
    "engine.lint": "lint_serving_engine: the donation/aliasing self-check",
}


class Span(NamedTuple):
    name: str
    start: float  # perf_counter seconds
    end: float
    # (name, start) of the enclosing span() on this thread; None at the
    # top and for a Timer's span
    parent: Optional[Tuple[str, float]] = None
    ids: Optional[dict] = None  # span()'s keywords: request_id, slot, step


_LOCK = threading.Lock()
_SPANS: List[Span] = []
_ENABLED = False
_OPEN = threading.local()  # .stack: the recorded spans open on this thread


class _RecordedSpan:
    """span() under spans_enabled(): the annotation plus one buffer
    entry, stamped on ``perf_counter`` like a Timer's."""

    __slots__ = ("name", "ids", "start", "parent", "annotation")

    def __init__(self, name: str, ids: dict):
        if name not in SPANS:
            raise ValueError(
                f"span {name!r} is not in apex_tpu.observability.trace"
                ".SPANS: add it there with a line on what it covers")
        self.name, self.ids = name, ids
        self.annotation = TraceAnnotation(SPAN_PREFIX + name, **ids)

    def __enter__(self):
        stack = getattr(_OPEN, "stack", None)
        if stack is None:
            stack = _OPEN.stack = []
        self.parent = stack[-1] if stack else None
        self.annotation.__enter__()
        self.start = time.perf_counter()
        stack.append((self.name, self.start))
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        _OPEN.stack.pop()
        self.annotation.__exit__(*exc)
        record_span(self.name, self.start, end, self.parent, self.ids)
        return False


def span(name: str, **ids):
    """A context manager round one interval of the program's host code:
    ``with span("engine.decode", active=n): ...``. ``name`` is a key of
    :data:`SPANS` (checked under :func:`spans_enabled`); ``ids`` are the
    identifiers that tie spans together (``request_id``, ``slot``,
    ``step``). See the module docstring for where the span lands."""
    if _ENABLED:
        return _RecordedSpan(name, ids)
    return TraceAnnotation(SPAN_PREFIX + name, **ids)


def spans_enabled() -> bool:
    return _ENABLED


def record_span(name: str, start: float, end: float,
                parent: Optional[Tuple[str, float]] = None,
                ids: Optional[dict] = None) -> None:
    if not _ENABLED:
        return
    with _LOCK:
        _SPANS.append(Span(name, start, end, parent, ids))


def _install_timer_hook(on: bool) -> None:
    from apex_tpu.utils import timers
    timers.set_span_hook(record_span if on else None)


def enable_spans() -> None:
    global _ENABLED
    _ENABLED = True
    _install_timer_hook(True)


def disable_spans() -> None:
    global _ENABLED
    _ENABLED = False
    _install_timer_hook(False)
    # drop undrained spans: a later session must not inherit them (and
    # mislabel them with its own step numbers)
    with _LOCK:
        _SPANS.clear()


def drain_spans() -> List[Span]:
    with _LOCK:
        out = list(_SPANS)
        _SPANS.clear()
    return out


@contextlib.contextmanager
def span_recording():
    """Enable span capture for a region (e.g. the whole training loop)."""
    was = _ENABLED
    enable_spans()
    try:
        yield
    finally:
        if not was:
            disable_spans()


def epoch_offset() -> float:
    """``time.time() − time.perf_counter()`` — the translation from this
    process's ``perf_counter`` timebase to the shared unix epoch.

    Every span/tick in the Chrome exports is stamped in ``perf_counter``
    seconds, whose zero point is *process-local* and arbitrary: two
    ranks' traces loaded together would land decades apart (or overlap
    meaninglessly). Stamping this offset into each trace's metadata
    makes the per-rank timebases recoverable after the fact, so
    :func:`merge_chrome_traces` can re-stamp every event onto one shared
    (epoch) timeline for a multi-rank Perfetto view. Sampled at call
    time; the two clocks drift only at NTP-slew rates, far below span
    resolution over a trace's lifetime."""
    return time.time() - time.perf_counter()


def trace_metadata() -> dict:
    """The metadata block both Chrome exporters stamp into their
    documents: the clock the ``ts`` fields are in plus the epoch offset
    that aligns it across processes. The profiler stamps its events on
    the epoch too, so ``epoch_offset_s`` is also what lines a
    :func:`span_recording` export up with the ``apex:`` spans of a
    profiler capture of the same run."""
    return {"clock": "perf_counter", "epoch_offset_s": epoch_offset()}


def merge_chrome_traces(docs: Iterable[dict]) -> dict:
    """Merge per-rank Chrome-trace documents into one aligned view.

    Each input must carry ``metadata.epoch_offset_s`` (both exporters
    stamp it); every event's ``ts`` is shifted by its document's offset,
    so all events land on the shared epoch-microseconds timeline —
    cross-rank ordering becomes meaningful even though each rank stamped
    its own ``perf_counter``. A document missing the offset raises —
    silently merging unaligned timebases is the bug this function
    exists to prevent.

    Pids: both exporters default to ``pid=0``, so two ranks' files
    usually COLLIDE — merged as-is their spans would interleave in one
    indistinguishable lane. When any pid appears in more than one
    document, every ``(document, pid)`` pair is re-stamped to a fresh
    pid (document order, then pid order), keeping each source's
    internal pid structure while separating the sources; collision-free
    inputs keep their pids verbatim. The merged document's metadata
    records ``clock: "epoch"`` with offset 0.
    """
    docs = list(docs)
    for i, doc in enumerate(docs):
        meta = doc.get("metadata") or {}
        if "epoch_offset_s" not in meta:
            raise ValueError(
                f"trace document {i} carries no metadata.epoch_offset_s "
                f"— cannot align its process-local perf_counter timebase")
    doc_pids = [{ev.get("pid", 0) for ev in doc.get("traceEvents", [])}
                for doc in docs]
    seen: set = set()
    collide = False
    for pids in doc_pids:
        if pids & seen:
            collide = True
            break
        seen |= pids
    remap: dict = {}
    if collide:
        for i, pids in enumerate(doc_pids):
            for p in sorted(pids, key=repr):
                remap[(i, p)] = len(remap)
    events: List[dict] = []
    for i, doc in enumerate(docs):
        shift_us = float(doc["metadata"]["epoch_offset_s"]) * 1e6
        for ev in doc.get("traceEvents", []):
            ev = dict(ev)
            if "ts" in ev:
                ev["ts"] = ev["ts"] + shift_us
            if collide:
                ev["pid"] = remap[(i, ev.get("pid", 0))]
            events.append(ev)
    events.sort(key=lambda e: e.get("ts", 0.0))
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "metadata": {"clock": "epoch", "epoch_offset_s": 0.0}}


def chrome_trace_events(spans, pid: int = 0, tid: int = 0,
                        step: Optional[int] = None) -> List[dict]:
    """Convert spans to Chrome-trace complete events (``ph="X"``, micro-
    second timestamps). ``step``, when given, lands in ``args`` so the
    viewer can filter by training step; a :func:`span`'s ``ids`` and its
    parent (``parent``: the name, ``parent_ts``: its start) land there
    too."""
    events = []
    for s in spans:
        ev = {"name": s.name, "ph": "X", "cat": "apex_tpu",
              "ts": s.start * 1e6, "dur": (s.end - s.start) * 1e6,
              "pid": pid, "tid": tid}
        args = dict(s.ids or {})
        if step is not None:
            args.setdefault("step", step)
        if s.parent is not None:
            args["parent"], args["parent_ts"] = s.parent[0], s.parent[1] * 1e6
        if args:
            ev["args"] = args
        events.append(ev)
    return events

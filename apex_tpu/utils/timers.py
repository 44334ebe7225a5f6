"""Wall-clock timers + the profiling workflow.

Reference: the Megatron-style timers of
``reference:apex/transformer/pipeline_parallel/_timers.py:6-79`` (`_Timer`
with ``cuda.synchronize`` around start/stop, ``_Timers.write`` to
TensorBoard, ``_Timers.log``) and the deprecated pyprof pipeline
(``reference:apex/pyprof``: NVTX-annotate -> nvprof -> attribute cost/op).

TPU re-design:

- ``Timer``/``Timers`` keep the reference API (start/stop/reset/elapsed,
  ``log``, ``write``) but synchronize by *fetching a value* from arrays you
  hand to ``stop(wait_for=...)`` — on an async backend a dispatch returns
  immediately, so the only honest fence is data materialization.
  Without ``wait_for`` the timer measures host wall time
  (dispatch cost), which is also meaningful and is what you want around
  blocking sections.
- pyprof's annotate->trace->attribute loop maps to ``jax.profiler``:
  annotations are ``jax.named_scope`` (emitted into HLO op metadata and
  visible in trace viewers and ``lower().as_text()``); the trace step is
  :func:`profile_trace` (a thin ``jax.profiler.trace`` wrapper); the
  attribution step is the trace viewer (tensorboard / xprof) or
  ``Compiled.cost_analysis()`` for a static FLOP/byte budget per program
  — the role of ``pyprof/prof``'s per-op flop counting.

Hot paths in this library are pre-annotated: DDP gradient allreduce
(``apex_ddp_allreduce``), SyncBatchNorm stats (``sync_bn_stats``), the
pipeline tick (``pipeline_tick``), and the flash-attention call
(``flash_attention``). A captured trace shows these names on the
corresponding fusions; ``scripts/check_annotations.py`` statically
verifies the set. For the structured per-step stream (metrics, not
traces) see :mod:`apex_tpu.observability` — its ``StepReporter`` can
snapshot these timers into TensorBoard/JSONL sinks and export their
start/stop spans as a Chrome trace (``docs/OBSERVABILITY.md``).

Typical workflow::

    from apex_tpu.utils.timers import Timers, profile_trace

    timers = Timers()
    with profile_trace("/tmp/trace"):      # step 2: capture
        for step in range(3):
            timers("fwd-bwd").start()
            grads = grad_fn(params, batch)
            timers("fwd-bwd").stop(wait_for=grads)
            timers("optimizer").start()
            params, opt_state = opt.step(grads, opt_state, params)
            timers("optimizer").stop(wait_for=params)
    timers.log(["fwd-bwd", "optimizer"])   # host-side summary
    # then: tensorboard --logdir /tmp/trace  (step 3: attribute)
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, Iterable, Optional

import jax
import numpy as np

__all__ = ["Timer", "Timers", "profile_trace", "device_fence",
           "set_span_hook"]

# Installed by apex_tpu.observability.trace when span capture is enabled:
# a callable (name, t0, t1) fed from every Timer.stop. Kept as a plain
# module global (not an import of observability) so the default cost is
# one None check per stop and there is no import cycle.
_SPAN_HOOK = None


def set_span_hook(hook) -> None:
    global _SPAN_HOOK
    _SPAN_HOOK = hook


def device_fence(tree: Any) -> None:
    """Block until the computation producing ``tree`` has finished, by
    fetching one element of one leaf: data on the host is a fence whatever
    the backend reports about readiness. One leaf suffices: device
    execution is stream-ordered, so materializing any output of the last
    queued program drains everything before it — and one fetch costs one
    host round trip instead of one per leaf."""
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "dtype") and getattr(leaf, "size", 0):
            np.asarray(jax.device_get(jax.numpy.ravel(leaf)[0:1]))
            return


class Timer:
    """``_Timer`` (``_timers.py:9-56``) with explicit device fencing."""

    def __init__(self, name: str):
        self.name = name
        self.elapsed_ = 0.0
        self.count_ = 0
        self.started_ = False
        self._t0 = 0.0

    def start(self) -> None:
        assert not self.started_, f"timer {self.name} already started"
        self._t0 = time.perf_counter()
        self.started_ = True

    def stop(self, wait_for: Any = None) -> None:
        assert self.started_, f"timer {self.name} is not started"
        if wait_for is not None:
            device_fence(wait_for)
        t1 = time.perf_counter()
        self.elapsed_ += t1 - self._t0
        self.count_ += 1
        self.started_ = False
        if _SPAN_HOOK is not None:
            _SPAN_HOOK(self.name, self._t0, t1)

    def reset(self) -> None:
        self.elapsed_ = 0.0
        self.count_ = 0
        self.started_ = False

    def elapsed(self, reset: bool = True) -> float:
        """Total elapsed seconds; restarts a running timer like the
        reference (``_timers.py:40-56``)."""
        was_running = self.started_
        if was_running:
            self.stop()
        out = self.elapsed_
        if reset:
            self.reset()
        if was_running:
            self.start()
        return out

    @contextlib.contextmanager
    def __call__(self, wait_for: Any = None):
        self.start()
        try:
            yield
        finally:
            self.stop(wait_for=wait_for)


class Timers:
    """``_Timers`` (``_timers.py:59-79``): a named group."""

    def __init__(self):
        self.timers: Dict[str, Timer] = {}

    def __call__(self, name: str) -> Timer:
        if name not in self.timers:
            self.timers[name] = Timer(name)
        return self.timers[name]

    def write(self, names: Iterable[str], writer, iteration: int,
              normalizer: float = 1.0, reset: bool = False) -> None:
        """Write to any object with ``add_scalar(tag, value, step)`` (the
        TensorBoard writer protocol, ``_timers.py:66-75``)."""
        assert normalizer > 0.0
        for name in names:
            value = self.timers[name].elapsed(reset=reset) / normalizer
            writer.add_scalar(name + "-time", value, iteration)

    def log(self, names: Optional[Iterable[str]] = None,
            normalizer: float = 1.0, reset: bool = True) -> str:
        """Format + print ``time (ms) | name: x.xx`` (``_timers.py:76-79``);
        returns the string (also printed) for testability."""
        assert normalizer > 0.0
        if names is None:
            names = list(self.timers)
        string = "time (ms)"
        for name in names:
            ms = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
            string += " | {}: {:.2f}".format(name, ms)
        print(string, flush=True)
        return string


@contextlib.contextmanager
def profile_trace(log_dir: str, host_tracer_level: int = 2):
    """``jax.profiler.trace`` wrapper — step 2 of the annotate -> trace ->
    attribute workflow (module docstring). View with tensorboard/xprof.

    The capture holds the device's ops and, on plane ``/host:CPU`` on the
    same clock, the program's own ``apex:`` spans
    (:func:`apex_tpu.observability.trace.span`: ``apex:sched.step``,
    ``apex:engine.decode``, ``apex:decode.wait``, ...), so a device idle
    gap can be put down to what the host was doing. ``host_tracer_level``
    is the profiler's own option: 0 records no annotation at all, 1 the
    ``apex:`` spans and other user annotations, 2 (default) and 3 the
    runtime's own finer ones too."""
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = host_tracer_level
    with jax.profiler.trace(log_dir, create_perfetto_link=False,
                            profiler_options=options):
        yield

"""Serving resilience: typed admission rejections, SLO-driven brownout,
and the serve-while-train checkpoint watcher.

PR 12 made the serving stack *measurable* (request tracing, latency
percentiles, SLO burn rate); this module plus the scheduler/engine
wiring makes it *survivable* — the PAPER's composable-wrapper philosophy
applied to the serving loop the way ``amp``/health hardened the training
loop. Four failure classes, each with a contract test:

- **overload** — ``SlotScheduler(max_queue=...)`` bounds the queue;
  :meth:`~apex_tpu.serving.scheduler.SlotScheduler.submit` returns a
  typed :class:`Rejection` (``reason="queue_full"``) instead of growing
  without bound, and the in-SLO goodput of ADMITTED requests stays
  comparable to an unloaded run (the load-shedding contract);
- **deadlines** — per-:class:`~apex_tpu.serving.scheduler.Request`
  ``deadline_ms`` (or the scheduler's ``default_deadline_ms``) expires
  requests while queued AND mid-flight (``finish_reason="expired"``,
  slot released through the AOT release program), plus
  ``cancel(request_id)``;
- **poison slots** — a quarantine engine
  (``ServingEngine(quarantine=True)``) checks the sampling-path logits
  per slot per decode step; a non-finite slot is retired alone
  (``finish_reason="poisoned"``) with a
  :class:`~apex_tpu.observability.health.CrashDump` flight record,
  instead of burning capacity on NaN context forever;
- **rollover** — ``SlotScheduler.drain(deadline_s=...)`` +
  ``ServingEngine.swap_params`` +
  :class:`CheckpointWatcher`: pick up the latest COMMITTED checkpoint
  from a live training run with zero recompiles (serve-while-train).

:class:`BrownoutPolicy` is the graceful-degradation hook between the
SLO tracker and admission: at burn rate > 1 (on track to violate), shed
new admissions and/or cap ``max_new_tokens`` — degrade, don't collapse.

Everything here is host-side; with every feature off the three AOT
serving programs are byte-identical to a pre-resilience engine's (the
established zero-cost idiom, asserted in ``tests/test_resilience.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

__all__ = ["Rejection", "REJECTION_REASONS", "BrownoutPolicy",
           "CheckpointWatcher", "watch_checkpoints"]

# the closed vocabulary of submit()-time rejections: queue_full (the
# max_queue bound), shed (BrownoutPolicy), draining (a drain() in
# progress), pool_exhausted (a paged engine whose KV block pool could
# never hold the prompt — transient pressure queues instead). Bad INPUT
# (empty/oversized prompt, non-positive deadline, duplicate in-flight
# id) still raises ValueError at the caller — a malformed request is a
# caller bug, not a load condition.
REJECTION_REASONS = ("queue_full", "shed", "draining", "pool_exhausted")


@dataclasses.dataclass(frozen=True)
class Rejection:
    """A typed admission refusal: WHY the request was not enqueued.

    Returned by :meth:`SlotScheduler.submit` (instead of the request id)
    so callers can branch on backpressure — retry with jitter on
    ``queue_full``, fail fast to the user on ``shed``, reroute to
    another replica on ``draining`` — without parsing exception text.
    Check with ``isinstance(r, Rejection)`` — NOT truthiness: request
    id 0 is a valid admission and ints make ``0`` falsy too, so ``if
    not sched.submit(req)`` would misread the first auto-id request as
    rejected. (A Rejection is still falsy, as a belt-and-suspenders for
    admitted-or-None flows, but isinstance is the contract.)"""

    reason: str
    request_id: Optional[int] = None
    detail: str = ""

    def __post_init__(self):
        if self.reason not in REJECTION_REASONS:
            raise ValueError(f"reason must be one of {REJECTION_REASONS}, "
                             f"got {self.reason!r}")

    def __bool__(self) -> bool:
        return False


class BrownoutPolicy:
    """SLO-driven graceful degradation: when the attached
    :class:`~apex_tpu.observability.slo.SLOTracker`'s burn rate crosses
    ``burn_threshold`` (1.0 = on track to violate the SLO), the
    scheduler's admission path consults this policy and either sheds the
    new request (``shed=True`` → :class:`Rejection(reason="shed")`,
    counted as ``serve/shed``) or caps its ``max_new_tokens`` at
    ``cap_max_new_tokens`` — shorter answers for everyone beats no
    answers for some. Both knobs may be combined; shedding wins.

    The engaged/disengaged state is re-evaluated per submission from the
    tracker's rolling window (O(targets) — the incremental counters the
    tracker already maintains) and exported as the 0/1 ``serve/brownout``
    gauge by the scheduler. No device work anywhere.
    """

    def __init__(self, tracker, *, burn_threshold: float = 1.0,
                 shed: bool = True,
                 cap_max_new_tokens: Optional[int] = None):
        if burn_threshold <= 0.0:
            raise ValueError("burn_threshold must be positive, "
                             f"got {burn_threshold!r}")
        if cap_max_new_tokens is not None and cap_max_new_tokens < 1:
            raise ValueError("cap_max_new_tokens must be >= 1, "
                             f"got {cap_max_new_tokens!r}")
        if not shed and cap_max_new_tokens is None:
            raise ValueError("a BrownoutPolicy with shed=False and no "
                             "cap_max_new_tokens would do nothing")
        self.tracker = tracker
        self.burn_threshold = float(burn_threshold)
        self.shed = bool(shed)
        self.cap_max_new_tokens = cap_max_new_tokens

    def engaged(self) -> bool:
        """True when the tracker's worst burn rate exceeds the
        threshold (NaN — an empty window — never engages: a cold server
        must admit; NaN > x is False)."""
        return self.tracker.max_burn_rate() > self.burn_threshold

    def cap(self, max_new_tokens: int) -> int:
        if self.cap_max_new_tokens is None:
            return max_new_tokens
        return min(max_new_tokens, self.cap_max_new_tokens)


class CheckpointWatcher:
    """Serve-while-train: roll the engine's weights onto the latest
    COMMITTED checkpoint step under ``run_dir`` (the
    :func:`~apex_tpu.checkpoint.save_checkpoint` layout a live
    :class:`~apex_tpu.elastic.runner.ElasticRunner` keeps appending to).

    :meth:`poll` is cheap when nothing changed (one ``latest_step``
    directory listing); when a NEW committed step appears it restores
    onto ``target`` (default: ``engine.params_spec``, the shapes and
    dtypes of the tree the engine was built on — the params-only
    checkpoint a serving deployment publishes), applies
    ``extract`` (for checkpoints whose state pytree nests the model
    params inside larger trainer state — pass the full-state ``target``
    and ``extract=lambda state: state[...]``), and calls
    ``engine.swap_params`` — zero recompiles, donation re-linted. Torn
    dirs (a writer died mid-save) are invisible by construction:
    ``latest_step`` only ever names COMMITTED steps, so the watcher can
    never roll onto a half-written checkpoint.

    Drive it from the serving loop's idle moments (e.g. between
    :meth:`~apex_tpu.serving.scheduler.SlotScheduler.step` calls, or
    after a ``drain()`` for a clean generation boundary). Each rollover
    ticks ``serve/swaps`` on ``registry`` (the process default when
    None — the same fallback the scheduler uses, so the documented
    counter moves without explicit wiring).
    """

    def __init__(self, engine, run_dir: str, *, target: Any = None,
                 extract: Optional[Callable[[Any], Any]] = None,
                 registry=None):
        from apex_tpu.observability import get_registry

        self.engine = engine
        self.run_dir = run_dir
        self.target = target
        self.extract = extract
        self.registry = registry if registry is not None \
            else get_registry()
        self.step: Optional[int] = None  # last step swapped in

    def poll(self) -> Optional[int]:
        """Swap in the newest COMMITTED step if it is newer than the
        last one swapped; returns that step, or None when nothing
        changed (including: no checkpoint exists yet — a serving process
        may outrun its trainer's first save)."""
        from apex_tpu.checkpoint import latest_step, restore_checkpoint

        step = latest_step(self.run_dir)
        if step is None or (self.step is not None and step <= self.step):
            return None
        target = self.target
        if target is None:
            target = self.engine.params_spec
        state, _ = restore_checkpoint(self.run_dir, target, step=step)
        params = self.extract(state) if self.extract is not None else state
        self.engine.swap_params(params)
        self.step = step
        self.registry.counter("serve/swaps").inc()
        return step


def watch_checkpoints(engine, run_dir: str, **kw) -> CheckpointWatcher:
    """Convenience spelling: ``watch_checkpoints(engine, run_dir)``
    builds the :class:`CheckpointWatcher` and performs one immediate
    :meth:`~CheckpointWatcher.poll` (rolling onto the latest COMMITTED
    step if one already exists)."""
    watcher = CheckpointWatcher(engine, run_dir, **kw)
    watcher.poll()
    return watcher

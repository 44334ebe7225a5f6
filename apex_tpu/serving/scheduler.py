"""Continuous slot batching: admit into freed slots, retire mid-flight.

The classic serving loop has a batch barrier — requests grouped into a
batch enter together and the batch ends when its LAST member finishes,
so every short sequence idles its slot while the longest one drags on.
This scheduler has none: the decode program always steps all
``max_seqs`` slots (fixed shape, zero recompiles), and between steps the
host admits queued requests into whatever slots just freed and retires
whatever finished — a sequence occupies hardware for exactly its own
lifetime. Occupancy under load approaches 100% of slots instead of the
~50% a barrier averages on mixed-length traffic.

Host-side state is deliberately tiny (per-slot last token, temperature,
budget counters); everything sequence-shaped lives in the device cache
behind its write cursor. The loop emits the ``serve/*`` host-registry
metric family (docs/OBSERVABILITY.md) each step.

**One decode step in flight.** ``step()`` dispatches decode step n+1
before it reads step n (:meth:`ServingEngine.decode` takes step n's
tokens on the device and returns them only after the dispatch), so the
host's fetch, harvest and planning run while the device works. The plan
leaves out each slot the host already knows step n ends (``length``
counting the unread token, ``capacity``, a dropped append, a passed
deadline); only an ``eos``, a cancel or a quarantine can leave a row
computed for a request that has retired, and its token is dropped
(``serve/decode_rows_discarded``). No token is recorded, and no stamp
taken, before its value is on the host. Device order keeps such a row
harmless: whatever the host later does with the slot's blocks or state
row reaches the device after it. The speculative loop stays synchronous:
its drafts need the tokens.

**Request lifecycle.** Every request carries a
:class:`~apex_tpu.observability.reqtrace.RequestRecord`: ``submit``
stamps the enqueue time, admission/prefill/decode/retire each stamp one
``time.perf_counter()`` per transition (the WHOLE hot-loop tracing
overhead — the device programs are untouched), so completions report
measured ``queue_wait_ms``/``ttft_ms``/``tpot_ms``/``e2e_ms`` and the
registry grows the matching ``serve/*`` latency histograms. Attaching a
:class:`~apex_tpu.observability.reqtrace.RequestTrace` (``trace=``)
additionally keeps retired records in its ring buffer (with per-tick
timestamps) for the Chrome-trace export; an
:class:`~apex_tpu.observability.slo.SLOTracker` (``slo=``) ingests each
retirement for goodput/burn-rate. Both default off and neither adds
device work (asserted in ``tests/test_reqtrace.py``).

**Phase timing.** :meth:`~SlotScheduler.step` and
:meth:`~SlotScheduler.submit` attach the registry to the thread
(:func:`~apex_tpu.observability.trace.timing_into`), so the spans of
``trace.TIMED`` observe their lengths into the histograms
``serve/span_ms/<name>``: ``sched.step``, ``engine.decode`` and
``decode.wait`` once a decode step; ``sched.admit``, ``engine.prefill``
and, where a decode step is in flight, ``prefill.drain`` (the prefill's
wait for that step) once an admission. Sums over counts of these give
the loop's phases without a profiler (docs/OBSERVABILITY.md "Program
spans").

**Resilience** (docs/SERVING.md "Resilience"; the policy objects live in
:mod:`apex_tpu.serving.resilience`): ``max_queue=`` bounds admission —
an over-limit ``submit`` returns a typed
:class:`~apex_tpu.serving.resilience.Rejection` instead of growing the
queue without bound; ``default_deadline_ms=`` / per-request
``deadline_ms`` expire requests while queued and mid-flight
(``finish_reason="expired"``) and :meth:`~SlotScheduler.cancel` removes
one by id; a quarantine engine retires a NaN-poisoned slot alone
(``finish_reason="poisoned"``, CrashDump flight record); ``brownout=``
sheds or caps admissions at SLO burn rate > 1; :meth:`~SlotScheduler
.drain` + :meth:`~SlotScheduler.swap_params` roll weights with zero
recompiles; ``fault_plan=`` scripts deterministic serving chaos
(:class:`~apex_tpu.elastic.faults.FaultPlan` ``poison_logits`` /
``slow_decode_s``). All host-side: every feature off leaves the three
AOT programs byte-identical (``tests/test_resilience.py``).

**Speculative decoding** (docs/SERVING.md "Speculative decoding"):
``speculate_k=k`` drives the engine's AOT ``verify`` program instead of
``decode`` — a host-side :class:`DraftSource` (default
:class:`NGramDraftSource`, prompt-lookup self-drafting, zero compiles)
proposes ``k`` tokens per active slot, one program dispatch scores the
whole window against the cached prefix, and each slot emits its
accepted prefix plus one correction/bonus token — 1 to ``k + 1`` tokens
per step. Greedy slots emit streams bitwise-identical to non-speculative
greedy; the ``serve/spec_*`` metric family tracks the acceptance rate
that decides whether ``k`` pays.
"""

from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from apex_tpu.observability import get_registry
from apex_tpu.observability.reqtrace import (LATENCY_BUCKETS_MS,
                                             RequestRecord)
from apex_tpu.observability.trace import span, timing_into
from apex_tpu.serving.cache import PoolExhausted
from apex_tpu.serving.resilience import Rejection

__all__ = ["Request", "Completion", "SlotScheduler", "DraftSource",
           "NGramDraftSource"]


class DraftSource:
    """Interface a speculative draft proposer implements: given a slot's
    full token context (prompt + everything generated so far), propose
    the next ``k`` tokens. Runs on the HOST between steps — a draft
    source never touches the compiled programs, so swapping sources (or
    later, backing one with a small draft model) is free of recompiles.
    Drafts are a pure throughput hint: a wrong draft costs its slot the
    rejected rows' compute, never correctness (the verify step's
    acceptance rule guarantees the output distribution)."""

    def draft(self, context: Sequence[int], k: int) -> List[int]:
        """Return exactly ``k`` proposed tokens to follow ``context``
        (``context`` is never empty — the prompt admitted)."""
        raise NotImplementedError


class NGramDraftSource(DraftSource):
    """Prompt-lookup / n-gram self-drafting (the zero-model draft
    source): find the longest suffix of the context — up to
    ``max_ngram`` tokens — that also occurred EARLIER in the context,
    and propose the ``k`` tokens that followed its most recent earlier
    occurrence (padded by repeating the last proposal when the match
    sits near the end). No match falls back to repeating the last
    context token. Repetitive text (code, templated prose, retrieval
    contexts) accepts most of these drafts; adversarially random text
    accepts few — the ``serve/spec_accept_rate`` gauge is the knob
    watcher."""

    def __init__(self, max_ngram: int = 3):
        if max_ngram < 1:
            raise ValueError(f"max_ngram must be >= 1, got {max_ngram}")
        self.max_ngram = int(max_ngram)

    def draft(self, context: Sequence[int], k: int) -> List[int]:
        ctx = [int(t) for t in context]
        n = len(ctx)
        for m in range(min(self.max_ngram, n - 1), 0, -1):
            suffix = ctx[n - m:]
            for start in range(n - m - 1, -1, -1):
                if ctx[start:start + m] == suffix:
                    out = ctx[start + m:start + m + k]
                    while len(out) < k:
                        out.append(out[-1])
                    return out
        return [ctx[-1]] * k


@dataclasses.dataclass
class Request:
    """One generation request. ``temperature`` <= 0 is greedy;
    ``eos_token`` (optional) stops generation early; ``max_new_tokens``
    always bounds it. ``deadline_ms`` (optional, > 0, measured from
    submission) expires the request both while queued and mid-flight —
    the scheduler's ``default_deadline_ms`` applies when None."""
    prompt: Sequence[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    eos_token: Optional[int] = None
    request_id: Optional[int] = None
    deadline_ms: Optional[float] = None


@dataclasses.dataclass
class Completion:
    """A finished request: the generated tokens (prompt excluded), why
    generation stopped (``"eos"`` | ``"length"`` | ``"capacity"`` |
    ``"expired"`` | ``"cancelled"`` | ``"poisoned"`` | ``"error"``), and
    the measured per-request latencies — ``queue_wait_ms`` (submit →
    slot), ``ttft_ms`` (submit → first token, queue wait included),
    ``tpot_ms`` (mean per-token after the first; None for single-token
    requests), ``e2e_ms`` (submit → retire). A request retired before
    admission (queued expiry/cancel) has no slot-side latencies and an
    empty token list."""
    request_id: int
    tokens: List[int]
    finish_reason: str
    queue_wait_ms: Optional[float] = None
    ttft_ms: Optional[float] = None
    tpot_ms: Optional[float] = None
    e2e_ms: Optional[float] = None


@dataclasses.dataclass
class _Active:
    request: Request
    generated: List[int]
    position: int            # prompt_len + len(generated), vs cache capacity
    record: RequestRecord
    deadline_t: Optional[float] = None  # perf_counter seconds, absolute


@dataclasses.dataclass
class _InFlight:
    """A dispatched decode step whose tokens the host has not read."""
    owners: Dict[int, _Active]   # slot -> the request its row computed for
    failed: List[int]            # slots whose append the pool dropped


# retirement reasons with their own dedicated counter next to the
# aggregate serve/retired (docs/OBSERVABILITY.md)
_REASON_COUNTERS = {"expired": "serve/expired",
                    "cancelled": "serve/cancelled",
                    "poisoned": "serve/poisoned",
                    "error": "serve/errors"}


class SlotScheduler:
    """See module docstring. Drive it with :meth:`submit` + :meth:`step`
    (one decode step per call), or :meth:`run` for a closed batch.

    ``trace`` (optional :class:`RequestTrace`) keeps retired request
    records in a bounded ring for Chrome-trace export / flight-recorder
    dumps; ``slo`` (optional :class:`SLOTracker`) ingests each
    retirement. With both None the only lifecycle cost left is one
    timestamp per transition — the latency fields on completions and the
    ``serve/*_ms`` histograms are always real measurements.

    Resilience knobs (all optional; see the module docstring and
    docs/SERVING.md "Resilience"): ``max_queue`` (admission bound),
    ``default_deadline_ms`` (deadline for requests that set none),
    ``brownout`` (a :class:`~apex_tpu.serving.resilience
    .BrownoutPolicy`), ``fault_plan`` (a :class:`~apex_tpu.elastic
    .faults.FaultPlan` with serving faults — a poison plan requires a
    quarantine engine and is refused here otherwise), ``dump_dir``
    (where poison-quarantine CrashDumps land).

    ``speculate_k=k`` (with an engine built ``speculate_k=k`` — the
    static window must agree) switches the loop onto the engine's AOT
    ``verify`` program: ``draft_source`` (default
    :class:`NGramDraftSource`) proposes ``k`` tokens per slot on the
    host, one dispatch verifies them all, and slots emit 1 to ``k + 1``
    tokens per step. Every other knob composes unchanged — deadlines and
    quarantine can retire a slot mid-harvest (the cursor only ever
    advanced by the accepted count, so nothing needs rolling back) and
    paged pool exhaustion retires the starved slot loudly."""

    def __init__(self, engine, registry=None, trace=None, slo=None, *,
                 max_queue: Optional[int] = None,
                 default_deadline_ms: Optional[float] = None,
                 brownout=None, fault_plan=None, dump_dir: str = ".",
                 speculate_k: int = 0,
                 draft_source: Optional[DraftSource] = None):
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1, got {max_queue}")
        if speculate_k:
            if engine.speculate_k != speculate_k:
                raise ValueError(
                    f"speculate_k={speculate_k} but the engine compiled "
                    f"speculate_k={engine.speculate_k} — "
                    "the verify program's window is static, so the "
                    "scheduler and engine must agree at construction")
        elif draft_source is not None:
            raise ValueError(
                "draft_source without speculate_k — pass speculate_k=k "
                "(matching the engine's) to enable speculative decoding")
        if default_deadline_ms is not None and default_deadline_ms <= 0:
            raise ValueError("default_deadline_ms must be positive, "
                             f"got {default_deadline_ms}")
        if (fault_plan is not None
                and getattr(fault_plan, "poison_logits", None)
                and not engine.quarantine):
            raise ValueError(
                "fault_plan schedules poison_logits but the engine has "
                "no quarantine check compiled in — the fault would be "
                "silently dropped; build the engine with quarantine=True")
        self.engine = engine
        self._reg = registry if registry is not None else get_registry()
        self.trace = trace
        self.slo = slo
        self.max_queue = max_queue
        self.default_deadline_ms = default_deadline_ms
        self.brownout = brownout
        self.fault_plan = fault_plan
        self.dump_dir = dump_dir
        self.speculate_k = int(speculate_k)
        self.draft_source = draft_source if draft_source is not None \
            else (NGramDraftSource() if speculate_k else None)
        self._spec_drafted = 0
        self._spec_accepted = 0
        self.queue: collections.deque = collections.deque()
        self.free: List[int] = list(range(engine.max_seqs))[::-1]
        self.active: Dict[int, _Active] = {}
        self.completed: List[Completion] = []
        self.steps = 0              # decode steps executed (fault keying)
        self.poison_dumps: List[str] = []
        self._tokens = np.zeros(engine.max_seqs, np.int32)
        self._temps = np.zeros(engine.max_seqs, np.float32)
        self._next_id = 0
        self._in_flight_ids = set()
        self._held: Optional[_InFlight] = None
        self._draining = False
        # deadline-free schedulers skip the per-step queue walk entirely
        self._any_deadlines = default_deadline_ms is not None
        self._tok_count = 0
        self._tok_t0: Optional[float] = None
        # the allocator's monotonic COW counter at the last step, so
        # serve/blocks_cow_copied emits deltas
        self._cow_seen = 0
        # pools by layer kind only: the window allocators' monotonic
        # (given, returned) at the last step
        self._window_seen = (0, 0)
        # a latent model only: the engine's monotonic count of latent
        # positions read at the last step
        self._latent_seen = 0
        # the most of the pool in use at the end of any step so far
        self._pool_peak = 0.0
        # how often the step in flight engages (docs/OBSERVABILITY.md):
        # at 0 from the start, so a run that never overlaps reads 0
        for name in ("serve/decode_steps_overlapped",
                     "serve/decode_rows_discarded"):
            self._reg.counter(name)
        # the serve/span_ms/* histograms: step() and submit() attach them
        # to the thread, so the loop's TIMED spans observe their lengths
        self._timing = timing_into(self._reg)

    # -- submission ---------------------------------------------------------

    def submit(self, request: Request) -> Union[int, Rejection]:
        """Enqueue ``request`` and return its id — or a falsy typed
        :class:`Rejection` under backpressure (``queue_full`` at the
        ``max_queue`` bound, ``shed`` from the brownout policy,
        ``draining`` during :meth:`drain`). Malformed input still
        RAISES: a load condition is the server's problem, a bad request
        is the caller's."""
        # an id the caller left out is the next one _submit will give
        with self._timing, span("sched.submit", request_id=self._next_id
                                if request.request_id is None
                                else request.request_id):
            return self._submit(request)

    def _submit(self, request: Request) -> Union[int, Rejection]:
        # validate HERE, not at admission: a bad request must bounce off
        # the caller, never kill the serving loop mid-step (by then it
        # has already been popped from the queue and other admissions
        # are half-done)
        if len(request.prompt) == 0:
            raise ValueError("empty prompt")
        if len(request.prompt) > self.engine.prefill_len:
            raise ValueError(
                f"prompt length {len(request.prompt)} exceeds the "
                f"engine's prefill window {self.engine.prefill_len}")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got "
                f"{request.max_new_tokens} (the prefill always samples "
                "one token)")
        if request.deadline_ms is not None and request.deadline_ms <= 0:
            raise ValueError(
                f"deadline_ms must be positive, got "
                f"{request.deadline_ms} (None means no deadline)")
        if (request.request_id is not None
                and request.request_id in self._in_flight_ids):
            raise ValueError(
                f"request_id {request.request_id} is already in flight "
                "(queued or active) — completions are keyed by id, so a "
                "duplicate would make one of them unaccountable")
        # backpressure: typed rejections, never unbounded growth
        if self._draining:
            self._reg.counter("serve/rejected").inc()
            return Rejection("draining", request.request_id,
                             "scheduler is draining in-flight requests")
        if (self.max_queue is not None
                and len(self.queue) >= self.max_queue):
            self._reg.counter("serve/rejected").inc()
            return Rejection("queue_full", request.request_id,
                             f"queue at max_queue={self.max_queue}")
        # admission control: a prompt that could never fit the WHOLE
        # pool is refused up front (queueing it would deadlock the queue
        # head forever); transient pressure — blocks held by in-flight
        # sequences — queues instead and _admit waits for retirements to
        # free blocks
        alloc = self.engine.allocator
        need = alloc.blocks_for(len(request.prompt))
        if need > alloc.num_blocks - 1:
            self._reg.counter("serve/rejected").inc()
            return Rejection(
                "pool_exhausted", request.request_id,
                f"prompt needs {need} blocks but the pool only has "
                f"{alloc.num_blocks - 1} allocatable")
        if self.brownout is not None:
            engaged = self.brownout.engaged()
            self._reg.gauge("serve/brownout").set(1.0 if engaged else 0.0)
            if engaged:
                if self.brownout.shed:
                    self._reg.counter("serve/shed").inc()
                    return Rejection(
                        "shed", request.request_id,
                        "SLO burn rate over the brownout threshold")
                capped = self.brownout.cap(request.max_new_tokens)
                if capped != request.max_new_tokens:
                    # cap a COPY: the caller's Request must not carry a
                    # transient brownout's truncation to its retries or
                    # to another replica
                    request = dataclasses.replace(
                        request, max_new_tokens=capped)
        if request.request_id is None:
            request.request_id = self._next_id
        self._next_id = max(self._next_id, request.request_id) + 1
        self._in_flight_ids.add(request.request_id)
        if request.deadline_ms is not None:
            self._any_deadlines = True
        # the enqueue stamp: queue wait is measured from here, not
        # inferred from admission order
        record = RequestRecord(request_id=request.request_id,
                               prompt_len=len(request.prompt),
                               submit_t=time.perf_counter())
        self.queue.append((request, record))
        return request.request_id

    @property
    def pending(self) -> int:
        return len(self.queue) + len(self.active)

    @property
    def draining(self) -> bool:
        return self._draining

    def _deadline_t(self, request: Request,
                    record: RequestRecord) -> Optional[float]:
        ms = request.deadline_ms if request.deadline_ms is not None \
            else self.default_deadline_ms
        return None if ms is None else record.submit_t + ms / 1e3

    # -- the loop -----------------------------------------------------------

    def _retire(self, slot: int, reason: str, now: float) -> None:
        st = self.active.pop(slot)
        # zero the cursor: an idle slot left deep in the cache would keep
        # paying full-prefix attention on every later decode step
        release_exc = None
        try:
            self.engine.release_slot(slot)
        except Exception as exc:
            # the HOST bookkeeping below (record retired, slot freed,
            # completion visible, id released) must complete regardless
            # — popping from active and then raising would strand the
            # slot and the request forever. On the "error" path the
            # engine is already known-broken (the failed dispatch may
            # have consumed the donated cache) and the original fault
            # is what propagates; on every other path the release
            # failure itself re-raises AFTER the books are straight.
            if reason != "error":
                release_exc = exc
        self.free.append(slot)
        self._in_flight_ids.discard(st.request.request_id)
        rec = st.record
        rec.retire_t = now
        rec.finish_reason = reason
        rec.generated = len(st.generated)
        self.completed.append(Completion(
            st.request.request_id, st.generated, reason,
            queue_wait_ms=rec.queue_wait_ms, ttft_ms=rec.ttft_ms,
            tpot_ms=rec.tpot_ms, e2e_ms=rec.e2e_ms))
        self._reg.counter("serve/retired").inc()
        if reason in _REASON_COUNTERS:
            self._reg.counter(_REASON_COUNTERS[reason]).inc()
        if rec.queue_wait_ms is not None:
            self._reg.histogram("serve/queue_wait_ms",
                                LATENCY_BUCKETS_MS).observe(
                                    rec.queue_wait_ms)
        if rec.ttft_ms is not None:
            self._reg.histogram("serve/ttft_ms",
                                LATENCY_BUCKETS_MS).observe(rec.ttft_ms)
        if rec.tpot_ms is not None:
            self._reg.histogram("serve/tpot_ms",
                                LATENCY_BUCKETS_MS).observe(rec.tpot_ms)
        if rec.e2e_ms is not None:
            self._reg.histogram("serve/e2e_ms",
                                LATENCY_BUCKETS_MS).observe(rec.e2e_ms)
        if self.trace is not None:
            self.trace.append(rec)
        if self.slo is not None:
            self.slo.observe(rec)
        if release_exc is not None:
            raise release_exc

    def _retire_queued(self, request: Request, record: RequestRecord,
                       reason: str, now: float) -> None:
        """Retire a request that never reached a slot (queued expiry or
        cancel): no slot-side latencies, empty token list, NOT counted
        as ``serve/retired`` (that counter means "slot freed") but under
        the reason's own counter; still observed by the trace ring and
        the SLO tracker (an expired request is a served-badly request —
        it must hurt goodput, not vanish from it)."""
        record.retire_t = now
        record.finish_reason = reason
        self._in_flight_ids.discard(request.request_id)
        self.completed.append(Completion(
            request.request_id, [], reason, e2e_ms=record.e2e_ms))
        if reason in _REASON_COUNTERS:
            self._reg.counter(_REASON_COUNTERS[reason]).inc()
        if self.trace is not None:
            self.trace.append(record)
        if self.slo is not None:
            self.slo.observe(record)

    def _expire_queued(self, now: float) -> None:
        if not self._any_deadlines:
            return  # nothing queued can ever expire: skip the walk
        kept: collections.deque = collections.deque()
        while self.queue:
            req, rec = self.queue.popleft()
            deadline = self._deadline_t(req, rec)
            if deadline is not None and now >= deadline:
                self._retire_queued(req, rec, "expired", now)
            else:
                kept.append((req, rec))
        self.queue = kept

    def _quarantine(self, slot: int, now: float) -> None:
        """Retire ONLY the poisoned slot (``finish_reason="poisoned"``,
        cursor zeroed through the same AOT release program as any
        retirement) and write a CrashDump-style flight record — the
        serving twin of the health monitor's non-finite dump. Every
        other slot keeps decoding untouched (the isolation contract:
        their greedy streams are identical to a fault-free run)."""
        from apex_tpu.observability.health import CrashDump

        st = self.active[slot]
        rec = st.record
        self._retire(slot, "poisoned", now)
        records = ([r.to_dict() for r in self.trace.last(16)]
                   if self.trace is not None else [rec.to_dict()])
        dump = CrashDump.from_payload(self.steps, dict(self._reg.snapshot()),
                                      requests=records)
        dump.config = {"slot": int(slot),
                       "request_id": int(st.request.request_id),
                       "prompt_len": int(rec.prompt_len),
                       "generated": int(rec.generated),
                       "finish_reason": "poisoned"}
        self.poison_dumps.append(dump.write(self.dump_dir,
                                            prefix="poison_dump"))

    def _abort_in_flight(self) -> None:
        """Exception-safety cleanup: a decode/prefill dispatch raised,
        so every in-flight request is retired ``finish_reason="error"``
        (records stamped, slots released where the engine still can,
        completions visible) before the error propagates — nothing is
        stranded in ``active`` holding a slot forever."""
        now = time.perf_counter()
        self._held = None     # its rows' requests retire here
        for slot in list(self.active):
            self._retire(slot, "error", now)

    def _finish_reason(self, st: _Active, tok: int) -> Optional[str]:
        req = st.request
        if req.eos_token is not None and tok == req.eos_token:
            return "eos"
        if len(st.generated) >= req.max_new_tokens:
            return "length"
        if st.position >= self.engine.max_len:
            return "capacity"
        return None

    def _record(self, tok: int, st: _Active, slot: int, now: float,
                is_tick: bool) -> None:
        st.generated.append(tok)
        st.position += 1
        self._tokens[slot] = tok
        self._tok_count += 1
        st.record.last_token_t = now
        if is_tick and self.trace is not None:
            st.record.decode_ts.append(now)
        reason = self._finish_reason(st, tok)
        if reason is not None:
            self._retire(slot, reason, now)

    def _build_drafts(self) -> np.ndarray:
        """The host drafting pass: one :meth:`DraftSource.draft` call
        per active slot over its full context (prompt + generated).
        Free slots draft zeros — their verify rows are masked inactive
        and their counts come back 0."""
        drafts = np.zeros((self.engine.max_seqs, self.speculate_k),
                          np.int32)
        for slot, st in self.active.items():
            ctx = list(st.request.prompt) + st.generated
            drafts[slot] = self.draft_source.draft(ctx, self.speculate_k)
        return drafts

    def _admit(self) -> int:
        admitted = 0
        while self.queue and self.free:
            req, rec = self.queue.popleft()
            now = time.perf_counter()
            deadline = self._deadline_t(req, rec)
            if deadline is not None and now >= deadline:
                # expired while waiting: never spend a prefill on it
                self._retire_queued(req, rec, "expired", now)
                continue
            if not self.engine.can_admit(req.prompt):
                # block-pool pressure: the blocks exist (submit
                # bounds the prompt to the pool) but in-flight
                # sequences hold them — requeue at the head and wait
                # for retirements to free blocks
                self.queue.appendleft((req, rec))
                break
            slot = self.free.pop()
            with span("sched.admit", request_id=req.request_id, slot=slot,
                      prompt_len=len(req.prompt)):
                if not self._admit_into(slot, req, rec, now, deadline):
                    break
            admitted += 1
        return admitted

    def _admit_into(self, slot: int, req: Request, rec: RequestRecord,
                    now: float, deadline: Optional[float]) -> bool:
        """Prefill ``req`` into ``slot`` (already taken off the free
        list) and record its first token; False when the pool ran out
        and the request went back to the head of the queue."""
        rec.admit_t = now
        rec.slot = slot
        try:
            first = self.engine.prefill(req.prompt, slot, req.temperature)
        except PoolExhausted:
            # can_admit is conservative but the shared-path COW
            # headroom can still miss by a block under extreme
            # pressure: requeue, never error-retire (host rolled
            # the partial allocation back)
            self.free.append(slot)
            self.queue.appendleft((req, rec))
            return False
        except Exception:
            # the popped request must not vanish: retire it as an
            # error (host bookkeeping only — the slot never held a
            # cursor) and surface the engine fault to the caller
            self.free.append(slot)
            self._retire_queued(req, rec, "error", now)
            raise
        # prefill() syncs on the sampled token, so this stamp is the
        # honest first-token time (prefill-done == first-token: the
        # admission program samples it)
        rec.prefill_done_t = rec.first_token_t = time.perf_counter()
        self._count_step_stats()
        st = _Active(req, [], len(req.prompt), rec, deadline_t=deadline)
        self.active[slot] = st
        self._temps[slot] = req.temperature
        self._reg.counter("serve/admitted").inc()
        self._reg.counter("serve/prefill_tokens").inc(len(req.prompt))
        if self.engine.rope_original:
            # the prompt's positions that YaRN's stretched table rotates
            self._reg.counter("serve/positions_past_rope_original").inc(
                max(0, len(req.prompt) - self.engine.rope_original))
        plan = self.engine.last_admit
        if plan.prefill:
            # the rows the prefill program computed, padding included
            self._reg.counter("serve/prefill_bucket_tokens").inc(
                self.engine.bucket_of(len(req.prompt)))
        else:
            # a prefix-shared admission: the shared span skipped
            # prefill entirely — serve/ttft_prefix_ms is the TTFT
            # histogram the acceptance bar compares against the
            # cold serve/ttft_ms population
            self._reg.counter("serve/prefix_hits").inc()
            self._reg.counter("serve/prefix_hit_tokens").inc(
                plan.shared_tokens)
            self._reg.histogram("serve/ttft_prefix_ms",
                                LATENCY_BUCKETS_MS).observe(
                (rec.first_token_t - rec.admit_t) * 1e3)
        # the prefill already sampled this request's first token —
        # it may even complete here (max_new_tokens == 1)
        self._record(first, st, slot, rec.first_token_t, is_tick=False)
        return True

    def step(self) -> int:
        """Expire what's overdue, admit whatever fits (skipped while
        draining), then run ONE decode step for the whole slot grid
        (skipped when nothing is active). Returns the number of tokens
        generated (prefill first-tokens included).

        Exception safety: a raised engine fault retires every in-flight
        request ``finish_reason="error"`` (slots released, records
        stamped, completions visible) before re-raising — a dead decode
        never strands ``active`` state."""
        with self._timing, span("sched.step", step=self.steps):
            if self._tok_t0 is None:
                self._tok_t0 = time.perf_counter()
            before = self._tok_count
            with span("sched.expire"):
                self._expire_queued(time.perf_counter())
            try:
                if not self._draining:
                    self._admit()
                if self.active or self._held is not None:
                    self._decode()
            except Exception:
                self._abort_in_flight()
                raise
            generated = self._tok_count - before
            with span("sched.gauges"):
                self._set_gauges(generated)
            return generated

    def _decode(self) -> None:
        """Plan and dispatch the next engine step from what the host
        knows, then harvest the step before it (``decode``: one step is
        always in flight) or the step itself (``verify``, which drafts
        from the tokens and so waits for them)."""
        held = self._held
        mask, overdue = self._plan(held)
        if not mask.any():
            self._settle()
        else:
            step_idx = self.steps + 1  # this decode step, 1-based
            poison = None
            if self.fault_plan is not None:
                self.fault_plan.before_decode(step_idx)
                pslot = self.fault_plan.poison_slot(step_idx)
                if pslot is not None:
                    poison = np.zeros(self.engine.max_seqs, np.float32)
                    poison[pslot] = np.nan
            owners = {int(s): self.active[s] for s in np.flatnonzero(mask)}
            if self.speculate_k:
                nxt, counts = self.engine.verify(
                    self._tokens, self._build_drafts(), self._temps,
                    mask, poison=poison)
                read = _InFlight(owners, list(self.engine.last_failed))
            else:
                counts = None
                nxt = self.engine.decode(self._tokens, self._temps,
                                         mask, poison=poison)
                read, self._held = held, _InFlight(
                    owners, list(self.engine.last_failed))
            self.steps = step_idx
            self._reg.counter("serve/decode_steps").inc()
            if read is not None:
                if counts is None:
                    self._reg.counter(
                        "serve/decode_steps_overlapped").inc()
                self._count_step_stats()
                with span("sched.harvest"):
                    self._harvest(nxt, counts, read)
        # mid-flight deadline enforcement: overdue slots retire after
        # their last sampled token is recorded
        now = time.perf_counter()
        for slot in overdue:
            if slot in self.active:
                self._retire(slot, "expired", now)
        if not self.active:
            self._settle()   # its rows are all for retired requests

    def _plan(self, held: Optional[_InFlight]):
        """The next step's active mask: every active slot but those the
        host already knows end with their unread token in ``held``
        (length, capacity, a dropped append: they retire at its
        harvest) or are past their deadline (returned as ``overdue``,
        to expire after that harvest). A slot AT capacity with nothing
        unread retires here: its append would be dropped (the pool has
        no block to give), so one more step would sample a token whose
        KV never landed. Only ``eos``, a cancel and a quarantined slot
        can leave a row computed for a request that has retired."""
        mask = np.zeros(self.engine.max_seqs, np.bool_)
        overdue = []
        now = time.perf_counter()
        for slot, st in list(self.active.items()):
            unread = held is not None and held.owners.get(slot) is st
            if unread and slot in held.failed:
                continue
            if len(st.generated) + unread >= st.request.max_new_tokens:
                continue
            if st.position + unread >= self.engine.max_len:
                if not unread:
                    self._retire(slot, "capacity", now)
                continue
            if st.deadline_t is not None and now >= st.deadline_t:
                overdue.append(slot)
                continue
            mask[slot] = True
        return mask, overdue

    def _settle(self) -> None:
        """Read the decode step in flight, if any, and harvest it: after
        this no step is in flight."""
        held, self._held = self._held, None
        if held is None:
            return
        nxt = self.engine.settle()
        self._count_step_stats()
        with span("sched.harvest"):
            self._harvest(nxt, None, held)

    def _count_step_stats(self) -> None:
        """What a ``step_stats`` model counted in the program that just
        ran (it came back in the token fetch), summed over its rows and
        added to the counters the model names (``stats_names``, one a
        column)."""
        stats = self.engine.last_stats
        if stats is None:
            return
        for name, n in zip(self.engine.stats_names, stats.sum(axis=0)):
            self._reg.counter(f"serve/{name}").inc(int(n))

    def _harvest(self, nxt: np.ndarray, counts: Optional[np.ndarray],
                 step: _InFlight) -> None:
        """Record the new token(s) of every row of ``step`` whose request
        still holds its slot, and retire what finished, was poisoned or
        starved. A row whose request retired before its token was read
        (eos, cancel, quarantine, after the step was dispatched) is
        dropped: its token is never recorded."""
        finite = (self.engine.last_finite
                  if self.engine.quarantine else None)
        # ONE stamp for the whole grid's tick, taken once the tokens are
        # on the host — the per-transition overhead contract
        now = time.perf_counter()
        if counts is not None:
            self._reg.counter("serve/spec_steps").inc()
            drafted = len(step.owners) * self.speculate_k
            self._spec_drafted += drafted
            self._reg.counter("serve/spec_drafted").inc(drafted)
        accepted = discarded = 0
        for slot, st in step.owners.items():
            if self.active.get(slot) is not st:
                discarded += 1
                continue
            if finite is not None and not finite[slot]:
                # the poison-slot quarantine: retire ONLY this
                # slot; its sampled token is garbage-from-NaN and
                # is discarded, every neighbor harvests normally
                self._quarantine(slot, now)
                continue
            if counts is None:
                self._record(int(nxt[slot]), st, slot, now, is_tick=True)
                continue
            # speculative harvest: the accepted prefix plus one
            # correction/bonus token. The engine already advanced
            # this slot's cursor by EXACTLY counts[slot], so a
            # retirement mid-harvest (eos / length / capacity)
            # abandons only tokens whose KV sits above the
            # cursor — a re-admitted slot can never read a
            # drafted-but-rejected entry
            accepted += max(0, int(counts[slot]) - 1)
            for j in range(int(counts[slot])):
                self._record(int(nxt[slot, j]), st, slot, now,
                             is_tick=True)
                if slot not in self.active:
                    break
        if discarded:
            self._reg.counter("serve/decode_rows_discarded").inc(discarded)
        if counts is not None:
            self._spec_accepted += accepted
            if accepted:
                self._reg.counter("serve/spec_accepted").inc(
                    accepted)
            if self._spec_drafted:
                self._reg.gauge("serve/spec_accept_rate").set(
                    self._spec_accepted / self._spec_drafted)
        # slots the exhausted pool could not give a write block
        # retire loudly as "capacity" — this
        # step's sampled token is valid (the kernel merges the
        # current token in-flight) but its KV was dropped, so
        # one more step would decode against a hole. On the
        # speculative path a failed slot's window aimed at the
        # null block and its count came back 0, so it emitted
        # nothing this step before retiring
        for slot in step.failed:
            if self.active.get(slot) is step.owners[slot]:
                self._retire(slot, "capacity", now)

    def _set_gauges(self, generated: int) -> None:
        """The ``serve/*`` counters and gauges of one finished step."""
        self._reg.counter("serve/generated_tokens").inc(generated)
        self._reg.gauge("serve/queue_depth").set(len(self.queue))
        self._reg.gauge("serve/active_slots").set(len(self.active))
        # the tree the engine was built on against the image of it that
        # its programs read (equal where the image is the tree)
        self._reg.gauge("serve/weights_handed_bytes").set(
            self.engine.weights_handed_bytes)
        self._reg.gauge("serve/weights_held_bytes").set(
            self.engine.weights_held_bytes)
        alloc = self.engine.allocator
        self._reg.gauge("serve/pool_blocks_free").set(
            alloc.free_blocks)
        # used + utilization next to free: free blocks alone cannot
        # separate fragmentation from load (block 0 is the reserved
        # null block, so allocatable capacity is num_blocks - 1)
        capacity = alloc.num_blocks - 1
        used = capacity - alloc.free_blocks
        self._reg.gauge("serve/pool_blocks_used").set(used)
        utilization = used / capacity if capacity else 0.0
        self._reg.gauge("serve/pool_utilization").set(utilization)
        # a high-water mark: how much of the pool the traffic ever filled
        self._pool_peak = max(self._pool_peak, utilization)
        self._reg.gauge("serve/pool_utilization_max").set(self._pool_peak)
        if self.engine.by_kind:
            for kind, n in alloc.blocks_in_use.items():
                self._reg.gauge(f"serve/blocks_in_use/{kind}").set(n)
            given, returned = alloc.window_blocks()
            self._reg.counter("serve/window_blocks_given").inc(
                given - self._window_seen[0])
            self._reg.counter("serve/window_blocks_returned").inc(
                returned - self._window_seen[1])
            self._window_seen = (given, returned)
            if self.engine.has_state:
                held = alloc.state_slots_in_use
                self._reg.gauge("serve/state_slots_in_use").set(held)
                self._reg.gauge("serve/state_bytes_held").set(
                    held * self.engine.cache.state_bytes_per_slot)
        if self.engine.latent:
            # the live positions the latent decode kernel read, summed over
            # the steps (the engine's count is monotonic)
            self._reg.counter("serve/latent_positions_read").inc(
                self.engine.latent_positions_read - self._latent_seen)
            self._latent_seen = self.engine.latent_positions_read
        if alloc.cow_copies > self._cow_seen:
            self._reg.counter("serve/blocks_cow_copied").inc(
                alloc.cow_copies - self._cow_seen)
            self._cow_seen = alloc.cow_copies
        elapsed = time.perf_counter() - self._tok_t0
        if elapsed > 0:
            self._reg.gauge("serve/tokens_per_sec").set(
                self._tok_count / elapsed)

    # -- resilience surface -------------------------------------------------

    def cancel(self, request_id: int) -> bool:
        """Cancel one request by id, wherever it is: still queued (it
        just never admits) or mid-flight (retired now,
        ``finish_reason="cancelled"``, slot released). Returns False for
        an unknown/already-finished id — cancelling twice is a no-op,
        not an error (the client's disconnect usually races the
        completion)."""
        now = time.perf_counter()
        for i, (req, rec) in enumerate(self.queue):
            if req.request_id == request_id:
                del self.queue[i]
                self._retire_queued(req, rec, "cancelled", now)
                return True
        for slot, st in list(self.active.items()):
            if st.request.request_id == request_id:
                self._retire(slot, "cancelled", now)
                return True
        return False

    def drain(self, deadline_s: Optional[float] = None
              ) -> Dict[int, Completion]:
        """Graceful drain: stop admitting (concurrent :meth:`submit`
        calls get ``Rejection(reason="draining")``), keep stepping until
        every IN-FLIGHT request finishes, and return this drain's
        completions. Queued requests stay queued — after a weight swap
        they are served by the new weights, which is the rollover point
        of draining at all. ``deadline_s`` bounds the wait: leftovers
        retire ``finish_reason="expired"`` when it runs out — the drain
        budget is a deadline the SERVER imposed, so these are
        server-side failures that count against goodput
        (:data:`~apex_tpu.observability.slo.FAILED_REASONS`), unlike a
        user's :meth:`cancel`. Admission resumes when the method
        returns (``serve/drains`` counts calls)."""
        self._draining = True
        t0 = time.perf_counter()
        n0 = len(self.completed)
        try:
            while self.active:
                if (deadline_s is not None
                        and time.perf_counter() - t0 >= deadline_s):
                    now = time.perf_counter()
                    for slot in list(self.active):
                        self._retire(slot, "expired", now)
                    break
                self.step()
            self._settle()      # no decode step in flight past a drain
        finally:
            self._draining = False
        self._reg.counter("serve/drains").inc()
        return {c.request_id: c for c in self.completed[n0:]}

    def swap_params(self, new_params) -> None:
        """Hot weight swap through :meth:`ServingEngine.swap_params`
        (zero recompiles, structure/shape/dtype-checked, donation
        re-linted), counted as ``serve/swaps``. Safe mid-:meth:`run`:
        in-flight requests keep their old-weight KV prefix and finish
        under the new weights; call :meth:`drain` first for a clean
        generation boundary. The decode step in flight is read and
        harvested first: it ran on the old weights, every later step
        runs on the new."""
        self._settle()
        self.engine.swap_params(new_params)
        self._reg.counter("serve/swaps").inc()

    def drain_completed(self) -> List[Completion]:
        """Pop and return the completion buffer. A long-lived server
        driving :meth:`step` must drain this — completions (with their
        full token lists) accumulate until collected."""
        out, self.completed = self.completed, []
        return out

    def run(self, requests: Sequence[Request],
            max_steps: Optional[int] = None,
            no_recompile: bool = False) -> Dict[int, Completion]:
        """Submit ``requests``, loop :meth:`step` until all complete (or
        ``max_steps``), and return ``{request_id: Completion}`` for the
        completions of THIS run (requests finishing during it —
        including ones submitted before the call); earlier runs' results
        stay in :attr:`completed` until drained.

        Backpressure: a closed batch knows the rest of its work, so a
        ``queue_full`` rejection PACES the run — the request waits
        host-side and resubmits as the queue drains (the queue bound
        still holds throughout; silently dropping work a later step
        could serve would be a shedding decision the caller never
        made). ``shed``/``draining`` rejections are final and the
        request is dropped (counted on ``serve/shed``/``serve/
        rejected``), exactly as for a live ``submit`` caller.

        ``no_recompile=True`` wraps the loop in the analysis engine's
        :class:`~apex_tpu.analysis.program.recompile_guard`: after the
        first (warmup) iteration, any movement of the compile-storm
        counters raises ``AnalysisError`` — the serving loop's
        zero-recompile contract as a live assertion instead of a test-
        only one (the three programs are AOT-compiled at engine
        construction, so steady-state steps must never trace)."""
        from contextlib import nullcontext

        if no_recompile:
            from apex_tpu.analysis.program import recompile_guard
            guard = recompile_guard("SlotScheduler.run")
        else:
            guard = nullcontext()
        n0 = len(self.completed)
        waiting = collections.deque(requests)

        def feed():
            while waiting:
                if (self.max_queue is not None
                        and len(self.queue) >= self.max_queue):
                    # wait for the next step to drain the queue WITHOUT
                    # probing submit(): a paced retry is not a refused
                    # submission, so it must not tick serve/rejected
                    return
                res = self.submit(waiting[0])
                if isinstance(res, Rejection) \
                        and res.reason == "queue_full":
                    return  # raced the bound: resubmit after a step
                waiting.popleft()  # admitted, or finally rejected

        feed()
        steps = 0
        with guard:
            while self.pending or waiting:
                self.step()
                feed()
                steps += 1
                if no_recompile and steps == 1:
                    guard.rebase()  # first-dispatch host paths warmed
                if max_steps is not None and steps >= max_steps:
                    break
        return {c.request_id: c for c in self.completed[n0:]}

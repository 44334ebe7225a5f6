"""Serving fast path: KV-cached decode for the GPT model.

The inference half of the library (docs/SERVING.md): a global
:class:`~apex_tpu.serving.cache.PagedKVCache` block pool with a
host-side :class:`~apex_tpu.serving.cache.BlockAllocator` (refcounts,
prefix-hash sharing, copy-on-write), AOT-compiled prefill/decode steps
with the pool donated (:class:`~apex_tpu.serving.engine.ServingEngine`:
decode HBM traffic O(actual context), a slot holds blocks and not a
whole ``max_len``, shared prompt prefixes skip their prefill),
fixed-shape sampling (:mod:`~apex_tpu.serving.sampling`), and a
continuous slot batcher
(:class:`~apex_tpu.serving.scheduler.SlotScheduler`) emitting the
``serve/*`` metric family. The request-lifecycle observability layer
(per-request TTFT/TPOT/queue-wait tracing, the Chrome swimlane export,
and SLO goodput tracking) lives in
:mod:`apex_tpu.observability.reqtrace` /
:mod:`~apex_tpu.observability.slo` and is re-exported here for
wiring convenience (``SlotScheduler(engine, trace=..., slo=...)``).
The resilience layer (typed admission rejections, deadlines,
poison-slot quarantine, graceful drain + zero-recompile hot weight
swap, SLO brownout — docs/SERVING.md "Resilience") lives in
:mod:`~apex_tpu.serving.resilience` plus scheduler/engine wiring.

Speculative decoding (docs/SERVING.md "Speculative decoding"): at
``speculate_k=k`` the engine compiles a ``verify`` program that scores a
slot's last token plus ``k`` host-drafted tokens
(:class:`~apex_tpu.serving.scheduler.NGramDraftSource`, a
:class:`~apex_tpu.serving.scheduler.DraftSource`) in one pass and
appends the window with a k-token cache write — 1 to ``k + 1`` tokens
per step at one step's HBM cost, greedy streams bitwise-identical to
non-speculative greedy.
"""

from apex_tpu.observability.reqtrace import (RequestRecord, RequestTrace,
                                             chrome_request_trace)
from apex_tpu.observability.slo import (SLOTarget, SLOTracker,
                                        SLOViolationError)
from apex_tpu.serving.cache import (AdmitPlan, BlockAllocator,
                                    KindBlockAllocator, KindPagedKVCache,
                                    PagedKVCache, PoolExhausted, StepPlan,
                                    cache_bytes_per_slot,
                                    paged_block_bytes)
from apex_tpu.serving.engine import PagedServingEngine, ServingEngine
from apex_tpu.serving.resilience import (REJECTION_REASONS,
                                         BrownoutPolicy,
                                         CheckpointWatcher, Rejection,
                                         watch_checkpoints)
from apex_tpu.serving.sampling import sample_tokens, verify_tokens
from apex_tpu.serving.scheduler import (Completion, DraftSource,
                                        NGramDraftSource, Request,
                                        SlotScheduler)

__all__ = ["cache_bytes_per_slot", "ServingEngine",
           "PagedKVCache", "BlockAllocator", "KindPagedKVCache",
           "KindBlockAllocator", "AdmitPlan", "StepPlan",
           "PoolExhausted", "paged_block_bytes", "PagedServingEngine",
           "sample_tokens", "verify_tokens", "Completion", "Request",
           "SlotScheduler", "DraftSource", "NGramDraftSource",
           "RequestRecord", "RequestTrace", "chrome_request_trace",
           "SLOTarget", "SLOTracker", "SLOViolationError",
           "Rejection", "REJECTION_REASONS", "BrownoutPolicy",
           "CheckpointWatcher", "watch_checkpoints"]

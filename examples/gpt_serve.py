"""Continuous-batching GPT serving demo — the decode-side counterpart of
``gpt_pretrain.py`` (docs/SERVING.md).

Builds a small randomly-initialized GPT, compiles the AOT prefill/decode
steps once (donated KV cache), enqueues a mixed bag of requests (greedy
and sampled, different lengths), streams tokens as slots produce them,
and prints the ``serve/*`` metric summary — including the per-request
latency percentiles (TTFT/TPOT p50/p95/p99 off the ``serve/*_ms``
histograms) and the rolling goodput under a demo SLO, plus a per-slot
Chrome swimlane trace (``--trace-out``). On 2 slots and 6 requests the
log shows the continuous-batching shape: short requests retire and their
slots re-admit from the queue while long ones keep decoding.

    python examples/gpt_serve.py --max-seqs 2 --requests 6
"""

import argparse

import jax
import numpy as np

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.serving import (Rejection, Request, RequestTrace,
                              ServingEngine, SLOTarget, SLOTracker,
                              SlotScheduler)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--max-seqs", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--prefill-len", type=int, default=16)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--int8-cache", action="store_true",
                    help="quantized KV cache (per-(position,head) "
                         "scales); halves cache HBM per slot")
    ap.add_argument("--trace-out", default=None,
                    help="write the per-request Chrome trace (one "
                         "swimlane per slot) to this path")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="admission bound: submissions past this queue "
                         "depth get a typed Rejection(queue_full) "
                         "instead of growing the queue without bound "
                         "(docs/SERVING.md Resilience)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="default per-request deadline: requests expire "
                         "(finish_reason 'expired') while queued or "
                         "mid-flight once this budget elapses")
    ap.add_argument("--speculate-k", type=int, default=0,
                    help="speculative decoding: draft k tokens per slot "
                         "from the self-drafting n-gram source and "
                         "verify them in ONE step (docs/SERVING.md "
                         "'Speculative decoding'); prints the "
                         "acceptance rate and the TPOT delta against a "
                         "same-session non-speculative baseline")
    ap.add_argument("--ttft-slo-ms", type=float, default=5000.0,
                    help="demo SLO: TTFT p95 threshold")
    ap.add_argument("--tpot-slo-ms", type=float, default=1000.0,
                    help="demo SLO: TPOT p99 threshold")
    args = ap.parse_args(argv)

    cfg = GPTConfig(vocab_size=args.vocab, hidden_size=args.hidden,
                    num_layers=args.layers,
                    num_attention_heads=args.heads,
                    max_position_embeddings=args.max_len)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    import jax.numpy as jnp
    engine = ServingEngine(
        model, params, max_seqs=args.max_seqs, max_len=args.max_len,
        prefill_len=args.prefill_len, top_k=args.top_k,
        cache_dtype=jnp.int8 if args.int8_cache else jnp.bfloat16,
        speculate_k=args.speculate_k)
    print(f"engine: {args.max_seqs} slots x {args.max_len} tokens, "
          f"{engine.bytes_per_slot()} cache bytes/slot; a 16GB chip "
          f"would hold ~{engine.suggest_max_seqs(16 << 30)} slots")

    reg = MetricsRegistry()
    targets = (SLOTarget("ttft_ms", 95, args.ttft_slo_ms),
               SLOTarget("tpot_ms", 99, args.tpot_slo_ms))
    trace = RequestTrace(capacity=256)
    slo = SLOTracker(targets, registry=reg, trace=trace,
                     on_violation="skip")
    sched = SlotScheduler(engine, registry=reg, trace=trace, slo=slo,
                          max_queue=args.max_queue,
                          default_deadline_ms=args.deadline_ms,
                          speculate_k=args.speculate_k)

    def demo_requests():
        rng = np.random.RandomState(0)
        return [Request(prompt=rng.randint(
                            1, args.vocab,
                            size=1 + i % args.prefill_len).tolist(),
                        max_new_tokens=1 + (args.max_new_tokens
                                            * (i + 1)) // 2,
                        temperature=0.0 if i % 2 == 0 else 0.8)
                for i in range(args.requests)]

    rejections = []
    for i, req in enumerate(demo_requests()):
        res = sched.submit(req)
        if isinstance(res, Rejection):
            rejections.append(res)
            print(f"  req {i} rejected: {res.reason} ({res.detail})")

    # the steady-state loop runs under the analysis engine's
    # zero-recompile guard (docs/ANALYSIS.md): after the first (warmup)
    # step, any retrace of the serving programs raises loudly
    from apex_tpu.analysis import recompile_guard

    seen = {}
    steps = 0
    with recompile_guard("gpt_serve loop") as guard:
        while sched.pending:
            sched.step()
            steps += 1
            if steps == 1:
                guard.rebase()
            # stream: print each request's tokens as they extend
            for slot, st in sched.active.items():
                rid = st.request.request_id
                if len(st.generated) != seen.get(rid):
                    seen[rid] = len(st.generated)
                    print(f"  req {rid} (slot {slot}): "
                          f"{st.generated[-4:]} "
                          f"({len(st.generated)} tokens)")

    results = {c.request_id: c for c in sched.completed}
    for rid in sorted(results):
        c = results[rid]
        if c.queue_wait_ms is None:  # retired before admission
            print(f"req {rid}: {len(c.tokens)} tokens, "
                  f"finished by {c.finish_reason}")
            continue
        print(f"req {rid}: {len(c.tokens)} tokens, "
              f"finished by {c.finish_reason} "
              f"(wait {c.queue_wait_ms:.1f}ms, ttft {c.ttft_ms:.1f}ms, "
              f"e2e {c.e2e_ms:.1f}ms)")
    snap = {k: v for k, v in reg.snapshot().items()
            if k.startswith("serve/") and "_bucket_le_" not in k
            and not k.endswith(("_count", "_sum"))}
    print("serve/* summary:", {k: round(v, 1) for k, v in snap.items()})

    # the latency/SLO summary: percentiles off the serve/*_ms histograms,
    # goodput off the tracker.
    # LATENCY_BUCKETS_MS matters on the get-or-create: a histogram the
    # scheduler never touched (tpot with --max-new-tokens 1) must still
    # land on the documented latency grid, not DEFAULT_BUCKETS
    from apex_tpu.observability import LATENCY_BUCKETS_MS
    latency = {}
    for short, name in (("ttft", "serve/ttft_ms"),
                        ("tpot", "serve/tpot_ms"),
                        ("queue_wait", "serve/queue_wait_ms"),
                        ("e2e", "serve/e2e_ms")):
        hist = reg.histogram(name, LATENCY_BUCKETS_MS)
        latency.update({f"{short}_p{q}_ms": round(hist.percentile(q), 2)
                        for q in (50, 95, 99)})
    goodput = slo.goodput()
    print("latency percentiles (ms):",
          {k: v for k, v in latency.items()
           if k.startswith(("ttft", "tpot"))})
    print(f"goodput {goodput:.3f} under SLO "
          f"[{'; '.join(t.describe() for t in targets)}]")
    # the resilience counts (docs/SERVING.md "Resilience"): typed
    # rejections at the admission bound, expiries against the deadline
    full_snap = reg.snapshot()
    rejected = int(full_snap.get("serve/rejected", 0.0))
    expired = int(full_snap.get("serve/expired", 0.0))
    print(f"rejected {rejected} (typed: "
          f"{[r.reason for r in rejections]}), expired {expired} "
          f"(max_queue={args.max_queue}, deadline_ms={args.deadline_ms})")
    if args.trace_out:
        trace.write_chrome_trace(args.trace_out)
        print(f"chrome request trace ({len(trace)} records, one lane "
              f"per slot) -> {args.trace_out}")
    spec = None
    if args.speculate_k:
        # same-session A/B: the identical request mix on a
        # non-speculative engine gives the honest TPOT baseline (the
        # repetitive loops a greedy tiny model falls into are exactly
        # what the n-gram source predicts)
        base_engine = ServingEngine(
            model, params, max_seqs=args.max_seqs, max_len=args.max_len,
            prefill_len=args.prefill_len, top_k=args.top_k,
            cache_dtype=jnp.int8 if args.int8_cache else jnp.bfloat16)
        base_reg = MetricsRegistry()
        SlotScheduler(base_engine, registry=base_reg).run(demo_requests())
        base_tpot = base_reg.histogram(
            "serve/tpot_ms", LATENCY_BUCKETS_MS).percentile(50)
        accept = full_snap.get("serve/spec_accept_rate", 0.0)
        spec = {"k": args.speculate_k,
                "accept_rate": accept,
                "drafted": int(full_snap.get("serve/spec_drafted", 0)),
                "accepted": int(full_snap.get("serve/spec_accepted", 0)),
                "spec_steps": int(full_snap.get("serve/spec_steps", 0)),
                "tpot_p50_ms": latency["tpot_p50_ms"],
                "baseline_tpot_p50_ms": round(base_tpot, 2),
                "tpot_delta_ms": round(base_tpot
                                       - latency["tpot_p50_ms"], 2)}
        print(f"speculative: k={spec['k']}, accepted "
              f"{spec['accepted']}/{spec['drafted']} drafts "
              f"(rate {accept:.3f}) over {spec['spec_steps']} verify "
              f"steps; tpot p50 {spec['tpot_p50_ms']:.2f}ms vs "
              f"{spec['baseline_tpot_p50_ms']:.2f}ms non-speculative "
              f"(delta {spec['tpot_delta_ms']:+.2f}ms)")
    return {"completions": results, "metrics": snap, "latency": latency,
            "goodput": goodput, "slo": [t.describe() for t in targets],
            "rejected": rejected, "expired": expired,
            "rejections": rejections, "spec": spec}


if __name__ == "__main__":
    main()

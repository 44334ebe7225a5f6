"""Kind ``serve``: drives ``SlotScheduler.submit()/step()`` over the engine
the configuration's family builds (``ctx.family``, ``benchmark/families/``).

Open loop: the requests of ``benchmark/traffic.py`` are submitted when they
are due, whether or not earlier ones have finished; one thread, as the
scheduler is synchronous. Every time is the benchmark's own reading of the
host clock: a request's first token at the return of the ``engine.prefill``
that sampled it, its last at the return of the ``step()`` that retired it,
both counted from when the request was DUE, so the generator's lateness and
the queue wait are in them. After the window
has closed the requests still in flight are served to their end (late is
late, not wrong), the peak is read, the engine is freed, and the plain
reference scores a seeded sample of the finished requests, the longest
among them.
"""

import time

import numpy as np

from benchmark import traffic

REQUIRED_LIMITS = ("token_gap_mean", "short_streams", "unfinished")
TRACE_SECONDS = 3.0      # the traced part of a --trace 1 window
TRACE_AT = 0.4           # ... which starts at this share of the window
DRAIN_SECONDS = 60.0     # how long past the close an answer is waited for
WARM_REQUESTS = 4


def percentile(values, q):
    """The ``q``-th percentile by the nearest-rank rule (no interpolation:
    a tail is a request that happened)."""
    ordered = sorted(values)
    rank = max(1, int(np.ceil(q / 100.0 * len(ordered))))
    return ordered[rank - 1]


class Server:
    """The engine under its scheduler, with the benchmark's own spans
    round the calls into each layer."""

    def __init__(self, ctx):
        from apex_tpu.observability.registry import MetricsRegistry
        from apex_tpu.serving import SlotScheduler

        self.ctx = ctx
        self.engine = ctx.family.serve_engine(ctx.config, ctx.cell["engine"],
                                              ctx.seed)
        self.registry = MetricsRegistry()
        self.sched = SlotScheduler(self.engine, registry=self.registry,
                                   speculate_k=self.engine.speculate_k)
        self.decode_calls = []       # (t0, t1, the family's step_facts)
        self.prefill_calls = []      # (t0, t1, the same + prompt tokens)
        self.first_token_t = {}      # prompt -> clock at its prefill's return
        self.registry_at = {}        # point of the run -> registry.snapshot()
        self._wrap()

    def _wrap(self):
        engine, sched, ctx = self.engine, self.sched, self.ctx
        decode, prefill = engine.decode, engine.prefill
        step_facts = ctx.family.step_facts

        def timed_decode(*args, **kw):
            step = step_facts(engine, sched)
            t0 = time.perf_counter()
            with ctx.span("engine.decode"):
                out = decode(*args, **kw)
            self.decode_calls.append((t0, time.perf_counter(), step))
            return out

        def timed_prefill(prompt, *args, **kw):
            step = dict(step_facts(engine, sched), tokens=len(prompt))
            t0 = time.perf_counter()
            with ctx.span("engine.prefill"):
                out = prefill(prompt, *args, **kw)
            t1 = time.perf_counter()
            self.prefill_calls.append((t0, t1, step))
            self.first_token_t.setdefault(tuple(prompt), t1)
            return out

        engine.decode, engine.prefill = timed_decode, timed_prefill

    def forget(self):
        """Drop what the wrappers recorded so far (the warm-up's calls)."""
        self.decode_calls.clear()
        self.prefill_calls.clear()
        self.first_token_t.clear()
        self.registry_at.clear()

    def snapshot(self, point):
        """The scheduler's registry (``serve/*`` and whatever the program
        counts there) as it stands at ``point`` of the run."""
        self.registry_at[point] = self.registry.snapshot()

    def free(self):
        import jax
        for leaf in jax.tree_util.tree_leaves(
                (self.engine.params, self.engine.cache)):
            leaf.delete()
        self.engine = self.sched = None


def serve_window(ctx, server, arrivals, seconds):
    """Submit each request when it is due, step while anything is pending,
    close after the step in flight at ``seconds``, then serve what is in
    flight to its end. Returns per-request records (``due``, ``submit``,
    and for what came back ``first``, ``done``, ``completion``), the
    window's length, the tokens delivered inside it, the traced part's
    bounds and how many requests the loop had not yet submitted at the
    close."""
    from apex_tpu.serving import Request
    sched = server.sched
    records = {a.index: dict(due=a.due_s, arrival=a) for a in arrivals}
    emitted = {}
    nxt, traced, tracing = 0, None, False
    trace_from = TRACE_AT * seconds
    t0 = time.perf_counter()

    def submit(a):
        records[a.index]["submit"] = time.perf_counter() - t0
        sched.submit(Request(prompt=a.prompt,
                             max_new_tokens=a.max_new_tokens,
                             temperature=0.0, request_id=a.index))

    def collect():
        """What the clients hold after this step: the tokens of the live
        requests and of the finished ones."""
        for st in sched.active.values():
            emitted[st.request.request_id] = len(st.generated)
        now = time.perf_counter() - t0
        for c in sched.drain_completed():
            emitted[c.request_id] = len(c.tokens)
            records[c.request_id].update(completion=c, done=now)
        return sum(emitted.values())

    def stop_trace():
        tb = time.perf_counter()
        ctx.stop_trace()
        server.snapshot("trace_to")
        return dict(seconds=tb - ta, t_from=ta - t0, t_to=tb - t0,
                    decode=(n_dec, len(server.decode_calls)),
                    prefill=(n_pre, len(server.prefill_calls)))

    tokens = 0
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        if ctx.trace and traced is None and not tracing \
                and now >= trace_from:
            server.snapshot("trace_from")
            ctx.start_trace()
            tracing, ta = True, time.perf_counter()
            n_dec, n_pre = len(server.decode_calls), len(server.prefill_calls)
        with ctx.span("submit"):
            while nxt < len(arrivals) and arrivals[nxt].due_s <= now:
                submit(arrivals[nxt])
                nxt += 1
        if sched.pending:
            with ctx.span("scheduler.step"):
                sched.step()
            tokens = collect()
        elif nxt < len(arrivals):
            with ctx.span("idle_wait"):
                time.sleep(max(0.0, min(
                    arrivals[nxt].due_s - (time.perf_counter() - t0),
                    0.002)))
        else:
            time.sleep(0.002)
        if tracing and time.perf_counter() - ta >= TRACE_SECONDS:
            traced, tracing = stop_trace(), False
    window_s = time.perf_counter() - t0
    if tracing:
        traced = stop_trace()
    server.snapshot("close")
    # the window is closed: what was due inside it and is still owed is
    # submitted and waited for, and its latency counts the wait
    not_submitted = len(arrivals) - nxt
    for a in arrivals[nxt:]:
        submit(a)
    t_close = time.perf_counter()
    while sched.pending and time.perf_counter() - t_close < DRAIN_SECONDS:
        sched.step()
        collect()
    for rec in records.values():
        first = server.first_token_t.get(tuple(rec["arrival"].prompt))
        if first is not None:
            rec["first"] = first - t0
    return records, window_s, tokens, traced, not_submitted


def finished(rec):
    c = rec.get("completion")
    return (c is not None and c.finish_reason in ("length", "eos")
            and "first" in rec)


def check_sample(records, seed, size):
    """The requests the reference scores: drawn from the seed out of those
    that finished, the longest among them. Prompts and served streams."""
    ok = [i for i, rec in records.items() if finished(rec)]
    if not ok:
        return [], []
    length = lambda i: (len(records[i]["arrival"].prompt)
                        + len(records[i]["completion"].tokens))
    longest = max(ok, key=length)
    rest = [i for i in ok if i != longest]
    np.random.default_rng([seed & 0xFFFFFFFF, 77]).shuffle(rest)
    sample = [longest] + rest[:size - 1]
    return ([records[i]["arrival"].prompt for i in sample],
            [list(records[i]["completion"].tokens) for i in sample])


def run(ctx):
    cfg, cell, family = ctx.config, ctx.cell, ctx.family
    vocab = family.vocab(cfg)
    server = Server(ctx)
    ctx.mark("weights_and_engine")
    paths = server.engine.attention_paths()
    ctx.say(kind="serve", attention_paths=paths)

    # warm-up: every program the window uses, through the same entry
    from apex_tpu.serving import Request
    warm = traffic.serve_arrivals(
        dict(cell["traffic_params"], rate_per_s=float(WARM_REQUESTS)),
        vocab, ctx.seed + 1, 1.0)
    for a in warm:
        server.sched.submit(Request(prompt=a.prompt, max_new_tokens=8,
                                    temperature=0.0,
                                    request_id=10 ** 9 + a.index))
    while server.sched.pending:
        server.sched.step()
    server.sched.drain_completed()
    server.forget()

    arrivals = traffic.serve_arrivals(
        cell["traffic_params"], vocab, ctx.seed, ctx.seconds)
    server.snapshot("open")
    with ctx.no_compiles() as compiles:
        ctx.open_window()
        records, window_s, tokens, traced, not_submitted = serve_window(
            ctx, server, arrivals, ctx.seconds)
    peak = ctx.memory_peak_bytes()

    # -- end-to-end, over ALL requests due in the window ---------------------
    # a request that failed or never finished counts as the worst there is
    worst = (ctx.seconds + DRAIN_SECONDS) * 1e3
    ttft, tpot, lateness, queue_wait = [], [], [], []
    latencies = []           # (done, ttft, tpot or None) of the finished
    failed = short = 0
    for rec in records.values():
        if not finished(rec):
            failed += 1
            ttft.append(worst)
            tpot.append(worst)
            continue
        c = rec["completion"]
        late_ms = (rec["submit"] - rec["due"]) * 1e3
        lateness.append(late_ms)
        ttft.append((rec["first"] - rec["due"]) * 1e3)
        queue_wait.append((rec["due"], late_ms + c.queue_wait_ms))
        per_token = None
        if len(c.tokens) > 1:
            per_token = ((rec["done"] - rec["first"]) * 1e3
                         / (len(c.tokens) - 1))
            tpot.append(per_token)
        latencies.append((rec["done"], ttft[-1], per_token))
        short += len(c.tokens) != rec["arrival"].max_new_tokens
    ctx.say(window_s=window_s, requests_due=len(arrivals),
            requests_finished=len(arrivals) - failed, requests_failed=failed,
            tokens_in_window=tokens,
            not_submitted_before_close=not_submitted,
            compilations_in_window=compiles.count,
            generator_lateness_p95_ms=percentile(lateness, 95)
            if lateness else None,
            ttft_p50_ms=percentile(ttft, 50), tpot_p50_ms=percentile(tpot, 50),
            decode_steps=len(server.decode_calls),
            prefills=len(server.prefill_calls),
            **family.held_bytes(cfg, cell["engine"], server.decode_calls))
    end_to_end = {
        "serve_tokens_per_s": tokens / window_s,
        "ttft_p95_ms": percentile(ttft, 95),
        "tpot_p95_ms": percentile(tpot, 95),
    }
    facts = dict(kind="serve", traced=traced, queue_wait_ms=queue_wait,
                 latencies_ms=latencies,
                 decode_calls=server.decode_calls,
                 prefill_calls=server.prefill_calls,
                 registry=server.registry_at)
    prompts, streams = check_sample(records, ctx.seed,
                                    cell["check"]["requests"])
    server.free()
    del server
    checks = score(ctx, prompts, streams)
    checks["short_streams"] = (float(short),
                               f"{short} of {len(arrivals) - failed}")
    checks["unfinished"] = (float(failed), f"{failed} of {len(arrivals)}")
    if not all(p == "pallas" for p in paths.values()) and not ctx.rehearse:
        checks["xla_attention_programs"] = (1.0, str(paths))
    return dict(attempted=len(arrivals), failed=failed,
                end_to_end=end_to_end, checks=checks,
                compilations=compiles.count, facts=facts,
                memory_peak_bytes=peak)


def score(ctx, prompts, streams, control=False):
    """How far the served tokens' logits lie below the reference's best at
    their positions: the mean over the scored tokens and the widest single
    gap (``control``: ``"int8"`` or ``"fp8"``, the same for the token that
    forward puts first)."""
    import jax
    cfg, eng, family = ctx.config, ctx.cell["engine"], ctx.family
    if not prompts:
        return {"token_gap_mean": (float(10 ** 9), "no request finished")}
    t_ref = time.perf_counter()
    width = max(len(p) + len(s) for p, s in zip(prompts, streams))
    width = min(eng["max_len"], -(-width // 128) * 128)
    ref = family.serve_reference(cfg, width, control=control)
    lo, hi = family.seed_key(ctx.seed)
    w = jax.jit(lambda lo, hi: family.make_weights(cfg, lo, hi))(lo, hi)
    served, ctrl = ref.gaps(w, prompts, streams)
    gaps = np.concatenate(served).astype(np.float64)
    exact = int((gaps == 0).sum())
    detail = (f"{gaps.size} tokens of {len(prompts)} requests, {exact} the "
              "reference's argmax")
    ctx.say(reference_s=time.perf_counter() - t_ref,
            scored_requests=len(prompts), scored_tokens=gaps.size,
            tokens_that_are_the_reference_argmax=exact,
            widest_gap=float(gaps.max()), mean_gap=float(gaps.mean()))
    # the mean over the scored tokens is what a lower precision moves: it
    # flips more near-ties, each by more. The widest single gap swings from
    # seed to seed by its nature and is printed beside it.
    out = {"token_gap_mean": (float(gaps.mean()), detail),
           "token_gap": (float(gaps.max()), "widest single gap")}
    if control:
        low = np.concatenate(ctrl).astype(np.float64)
        out["control_token_gap_mean"] = (
            float(low.mean()), f"{int((low > 0).sum())} of {low.size} "
            "positions flipped")
        out["control_token_gap"] = (float(low.max()), "widest single gap")
    return out

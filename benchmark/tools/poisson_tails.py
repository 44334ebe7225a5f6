"""Set true Poisson traffic beside a serve cell's low-variance stand-in,
once, on the chip: one engine; one window of the cell's own arrivals
(``traffic.serve_arrivals``: the distributions' quantiles, every seed the
same work), then for each seed a window of i.i.d. draws at the same rate
(exponential gaps, log-normal lengths clipped as the cell says, all drawn
from the seed: the count, the work, bursts and runs of long requests vary).
Per window the tails, the slots in use and how many requests had to wait
for a slot. ``--long S`` adds one i.i.d. window of S seconds.

    python3 benchmark/tools/poisson_tails.py --workload <cell> --seeds 1,2,3 --seconds 51 --long 150
"""

import argparse
import json
import math
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as harness, traffic  # noqa: E402

WAITED_MS = 50.0       # a submit-to-slot wait above this was a wait for a slot


def iid_lengths(rng, spec, n):
    x = np.exp(math.log(spec["median"])
               + spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def iid_arrivals(params, vocab_size, seed, seconds):
    """A Poisson process of ``rate_per_s`` over ``seconds`` (the first
    request due at 0, as the stand-in's) with i.i.d. clipped log-normal
    lengths."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 3])
    gaps = rng.exponential(1.0 / params["rate_per_s"],
                           int(4 * params["rate_per_s"] * seconds) + 16)
    due = np.cumsum(gaps) - gaps[0]
    due = due[due < seconds]
    prompts = iid_lengths(rng, params["prompt_len"], len(due))
    outputs = iid_lengths(rng, params["output_len"], len(due))
    return [traffic.Arrival(i, float(due[i]),
                            rng.integers(1, vocab_size,
                                         int(prompts[i])).tolist(),
                            int(outputs[i]))
            for i in range(len(due))]


def window(ctx, server, serve, arrivals, seconds):
    server.forget()
    records, window_s, tokens, _, _ = serve.serve_window(
        ctx, server, arrivals, seconds)
    ttft, tpot, waits, unfinished = [], [], [], 0
    for rec in records.values():
        if not serve.finished(rec):
            unfinished += 1
            continue
        c = rec["completion"]
        ttft.append((rec["first"] - rec["due"]) * 1e3)
        waits.append(c.queue_wait_ms)
        if len(c.tokens) > 1:
            tpot.append((rec["done"] - rec["first"]) * 1e3
                        / (len(c.tokens) - 1))
    calls = [c for c in server.decode_calls if c[1] - c[0] > 0]
    in_window = [len(c[2]["contexts"]) for c in calls]
    return {
        "requests": len(arrivals), "unfinished_after_drain": unfinished,
        "offered_tokens_per_s": sum(a.max_new_tokens for a in arrivals)
        / seconds,
        "tokens_per_s": tokens / window_s,
        "ttft_p50_ms": serve.percentile(ttft, 50),
        "ttft_p95_ms": serve.percentile(ttft, 95),
        "ttft_p99_ms": serve.percentile(ttft, 99),
        "ttft_max_ms": max(ttft),
        "tpot_p95_ms": serve.percentile(tpot, 95),
        "waited_for_a_slot": sum(w > WAITED_MS for w in waits),
        "slot_wait_max_ms": max(waits),
        "active_slots_mean": sum(in_window) / len(in_window),
        "active_slots_most": max(in_window),
        "decode_roundtrip_median_ms": sorted(
            (b - a) * 1e3 for a, b, _ in calls)[len(calls) // 2],
        **ctx.family.held_bytes(ctx.config, ctx.cell["engine"], calls)}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--long", type=float, default=0.0)
    ap.add_argument("--data", default=harness.HERE)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax

    from benchmark.kinds import serve
    cell, config = harness.load_cell(args.data, args.workload)
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        sys.exit("poisson_tails needs the chip")
    harness.enable_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    ctx = harness.quiet_context(cell, config, devices[:1], seeds[0],
                                args.seconds, args.rehearse)
    server = serve.Server(ctx)
    params, vocab = cell["traffic_params"], ctx.family.vocab(config)
    plan = [("stand_in", seeds[0], args.seconds)] \
        + [("iid", seed, args.seconds) for seed in seeds]
    if args.long:
        plan.append(("iid", seeds[-1] + 1, args.long))
    for draws, seed, seconds in plan:
        make = traffic.serve_arrivals if draws == "stand_in" \
            else iid_arrivals
        arrivals = make(params, vocab, seed, seconds)
        print(json.dumps(dict(
            draws=draws, seed=seed, seconds=seconds,
            **window(ctx, server, serve, arrivals, seconds))), flush=True)
    server.free()


if __name__ == "__main__":
    main()

"""Find the knee of a serve cell once, on the chip: one engine, the cell's
traffic offered at each of a few rates for ``--seconds`` each; per rate the
completed tokens per second, the tails, and what was still pending when the
window closed (a backlog that grows with the window is above the knee).
``--iid`` offers true Poisson draws (``poisson_tails.iid_arrivals``) in
place of the cell's stand-in: with a long window, the knee in the steady
state.

    python3 benchmark/tools/sweep_rate.py --workload <cell> --rates 6,8,10 --seconds 20
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as harness, traffic  # noqa: E402
from benchmark.kinds import serve  # noqa: E402
from benchmark.tools import poisson_tails  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--data", default=harness.HERE)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax
    cell, config = harness.load_cell(args.data, args.workload)
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        sys.exit("sweep_rate needs the chip")
    harness.enable_cache()
    ctx = harness.quiet_context(cell, config, devices[:1], args.seed,
                                args.seconds, args.rehearse)
    server = serve.Server(ctx)
    vocab = ctx.family.vocab(config)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(cell["traffic_params"], rate_per_s=rate)
        make = poisson_tails.iid_arrivals if args.iid \
            else traffic.serve_arrivals
        arrivals = make(mix, vocab, args.seed + i, args.seconds)
        server.forget()
        records, window_s, tokens, _, not_submitted = serve.serve_window(
            ctx, server, arrivals, args.seconds)
        ttft, tpot, pending_at_close, unfinished = [], [], 0, 0
        for idx in sorted(records):
            rec = records[idx]
            if not serve.finished(rec):
                unfinished += 1
                ttft.append(float("inf"))
                continue
            n = len(rec["completion"].tokens)
            ttft.append((rec["first"] - rec["due"]) * 1e3)
            if n > 1:
                tpot.append((rec["done"] - rec["first"]) * 1e3 / (n - 1))
            pending_at_close += rec["done"] > window_s
        half, n = ttft, len(ttft)
        print(json.dumps({
            "rate_per_s": rate, "requests": n,
            "tokens_per_s": tokens / window_s,
            "offered_tokens_per_s": sum(a.max_new_tokens for a in arrivals)
            / args.seconds,
            "pending_at_close": pending_at_close,
            "waited_for_a_slot": sum(
                rec["completion"].queue_wait_ms > poisson_tails.WAITED_MS
                for rec in records.values() if serve.finished(rec)),
            "unfinished_after_drain": unfinished,
            "prefill_median_ms": sorted(
                (b - a) * 1e3 for a, b, _ in server.prefill_calls)[
                    len(server.prefill_calls) // 2],
            "not_submitted_at_close": not_submitted,
            "ttft_p50_ms": serve.percentile(ttft, 50),
            "ttft_p95_ms": serve.percentile(ttft, 95),
            "ttft_p95_first_half_ms": serve.percentile(half[: n // 2], 95),
            "ttft_p95_second_half_ms": serve.percentile(half[n // 2:], 95),
            "tpot_p50_ms": serve.percentile(tpot, 50),
            "tpot_p95_ms": serve.percentile(tpot, 95),
            "decode_steps": len(server.decode_calls),
            "decode_roundtrip_median_ms": sorted(
                (b - a) * 1e3 for a, b, _ in server.decode_calls)[
                    len(server.decode_calls) // 2],
            "mean_active_slots": sum(len(step["contexts"]) for _, _, step
                                     in server.decode_calls)
            / max(1, len(server.decode_calls)),
        }), flush=True)


if __name__ == "__main__":
    main()

"""Read, on the chip at a serve cell's own size, what `correct` would compare
with each of the family's planted faults (``family.reference.FAULTS``) and
the fp8 control in the engine's place: one served window a seed, then the
plain reference scores the same sample once per fault. One JSON line a seed.
``benchmark/tools/calibrate.py`` reads the program and the two precision
controls; this reads the faults beside them (PERF.md section 2).

    python3 scripts/calibrate_serve_faults.py --workload <cell> --seeds 1,2,3 [--requests 6]

``--modes`` takes any control the family's reference knows (``fp8_routed``
of ``nemotron_h``: the routed experts' products alone in fp8); ``--set
key=number`` reads the same at another value of a configuration key (an
init scale such as ``routed_gain``), in program and reference alike.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import run as harness, traffic  # noqa: E402
from benchmark.kinds import serve  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--requests", type=int, default=None,
                    help="scored requests a seed (default: the cell's)")
    ap.add_argument("--modes", default=None,
                    help="comma-separated (default: fp8 and every fault)")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=NUMBER",
                    help="a configuration key at another value")
    ap.add_argument("--data", default=harness.HERE)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax
    cell, config = harness.load_cell(args.data, args.workload)
    for key, value in (item.split("=") for item in args.set):
        config[key] = float(value)
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        sys.exit("needs the chip")
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        ctx = harness.quiet_context(cell, config, devices[:cell["chips"]],
                                    seed, args.seconds, args.rehearse)
        modes = args.modes.split(",") if args.modes \
            else ["fp8"] + list(ctx.family.reference.FAULTS)
        server = serve.Server(ctx)
        arrivals = traffic.serve_arrivals(
            cell["traffic_params"], ctx.family.vocab(config), seed,
            args.seconds)
        records, *_ = serve.serve_window(ctx, server, arrivals, args.seconds)
        prompts, streams = serve.check_sample(
            records, seed, args.requests or cell["check"]["requests"])
        server.free()
        del server
        out = {"seed": seed, "cell": args.workload, "set": args.set,
               "scored_tokens": sum(map(len, streams))}
        for mode in modes:
            got = serve.score(ctx, prompts, streams, control=mode)
            out.setdefault("program", got["token_gap_mean"][0])
            out[mode] = got["control_token_gap_mean"][0]
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

"""Read the numbers a cell's ``correct`` compares, on the chip at the cell's
own size, over many seeds in one process: the program against the plain
reference (the lower reading of each limit), and on the first
``--controls`` seeds the control (the reference in int8) and the planted
faults against the reference (the upper reading). One JSON line a seed.

    python3 benchmark/tools/calibrate.py --workload <cell> --seeds 101,102,... --controls 3
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as harness, traffic  # noqa: E402


def values(checks):
    return {k: [v, str(d)] for k, (v, d) in checks.items()}


def train_seed(ctx, controls, program=True):
    from benchmark.kinds import train
    out = {}
    if program:
        job = train.Job(ctx)
        readings = train.first_steps(job)
        job.free()
        del job
        ref = train.follow_reference(
            ctx, against=train.read_gradient(ctx, readings))
        out = {"program": values(train.compare(readings, ref)),
               "ref_losses": ref["losses"],
               "ref_grad_norms": ref["grad_norms"]}
    dp = ctx.cell["job"]["dp"]
    sides = [("control_int8", dict(quant="int8")),
             ("fault_half_batch", dict(keep_share=0.5))]
    if dp > 1:
        sides.append(("fault_no_exchange", dict(keep_share=1.0 / dp)))
    for name, kw in sides:
        if not controls:
            break
        other = train.follow_reference(ctx, keep_first_grad=True, **kw)
        # the reference again, against that side's first gradient
        again = train.follow_reference(ctx,
                                       against=other.pop("first_grad"))
        out[name] = values(train.compare(other, again))
    return out


def serve_seed(ctx, controls, program=True):
    from benchmark.kinds import serve
    cell = ctx.cell
    server = serve.Server(ctx)
    arrivals = traffic.serve_arrivals(cell["traffic_params"],
                                      ctx.family.vocab(ctx.config), ctx.seed,
                                      ctx.seconds)
    records, *_ = serve.serve_window(ctx, server, arrivals, ctx.seconds)
    prompts, streams = serve.check_sample(records, ctx.seed,
                                          cell["check"]["requests"])
    server.free()
    del server
    out = {"program": values(serve.score(ctx, prompts, streams)),
           "finished": sum(map(serve.finished, records.values())),
           "due": len(arrivals)}
    for mode in ("int8", "fp8") if controls else ():
        got = serve.score(ctx, prompts, streams, control=mode)
        out["control_" + mode] = values(
            {k: v for k, v in got.items() if k.startswith("control_")})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--data", default=harness.HERE)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--skip-program", action="store_true",
                    help="train: read only the control and the faults")
    args = ap.parse_args(argv)
    import jax
    cell, config = harness.load_cell(args.data, args.workload)
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        sys.exit("calibrate needs the chip")
    harness.enable_cache()
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        ctx = harness.quiet_context(cell, config, devices[:cell["chips"]],
                                    seed, args.seconds, args.rehearse)
        one = (train_seed if cell["kind"] == "train" else serve_seed)(
            ctx, n < args.controls, not args.skip_program)
        print(json.dumps(dict(one, seed=seed, cell=args.workload)),
              flush=True)


if __name__ == "__main__":
    main()

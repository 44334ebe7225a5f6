"""One run of a serve cell through ``run.py``'s own ``main``, and the serving
loop's phases from the program's own histograms (``serve/span_ms/<name>``
of the scheduler's registry, ``apex_tpu.observability.trace.TIMED``).

    python3 benchmark/tools/serve_phases.py --workload <cell> --seed 7 [--trace 1] [--out FILE]

(``--rehearse``, ``--data`` and ``--seconds`` go on to ``run.py``.)

Printed to ``--out`` (default: standard error), beside ``run.py``'s own
result line on standard output:

- ``phases``: each timed span's count and mean ms over the window (``open``
  to ``close``) and, in a traced run, over its untraced first part
  (``open`` to ``trace_from``); None for a program without the histograms;
- ``stalls``: the ``decode.wait`` observations past the first bucket bound
  at or over ``STALL_MS`` over the window, read off the histogram's
  buckets at ``close`` (the operator's stall alarm);
- ``bench_calls``: medians, in ms, of the ``engine.decode`` and
  ``engine.prefill`` calls the benchmark's own wrapper timed, over the
  window's calls before the traced part (all of them in an untraced run);
- with ``--trace 1``, ``traced``: the mean ``prefill.wait`` and
  ``prefill.drain`` spans of the trace, the mean device time of the runs of
  the prefill programs (``XLA Modules``), and the device's idle seconds
  under ``apex:prefill.drain`` against that span's seconds.
"""

import argparse
import bisect
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as harness, trace_reduce  # noqa: E402
from benchmark.kinds import serve  # noqa: E402

TIMED = ("sched.step", "sched.admit", "engine.prefill", "prefill.drain",
         "engine.decode", "decode.wait")
HIST = "serve/span_ms/"
STALL_MS = 100.0


def phases(lo, hi):
    """``{span: {"count", "mean_ms"}}`` of the growth from snapshot ``lo``
    to ``hi``; None without the histograms."""
    if HIST + "engine.decode_count" not in hi:
        return None
    out = {}
    for name in TIMED:
        n = hi[f"{HIST}{name}_count"] - lo.get(f"{HIST}{name}_count", 0.0)
        s = hi[f"{HIST}{name}_sum"] - lo.get(f"{HIST}{name}_sum", 0.0)
        out[name] = {"count": n, "mean_ms": s / n if n else None}
    return out


def stalls(lo, hi, name="decode.wait"):
    """Observations of ``name`` past the first bucket bound at or over
    ``STALL_MS``, from ``lo`` to ``hi``: ``{"bound_ms", "count"}``."""
    head = f"{HIST}{name}_bucket_le_"
    bounds = sorted(float(k[len(head):]) for k in hi
                    if k.startswith(head) and not k.endswith("_inf"))
    over = [b for b in bounds if b >= STALL_MS]
    if not over:
        return None
    key = head + f"{over[0]:g}"
    grown = lambda k: hi[k] - lo.get(k, 0.0)
    return {"bound_ms": over[0],
            "count": grown(f"{HIST}{name}_count") - grown(key)}


def bench_calls(facts):
    """Medians, in ms, of the wrapper's calls before the traced part."""
    traced = facts.get("traced")
    out = {}
    for kind in ("decode", "prefill"):
        calls = facts[kind + "_calls"]
        if traced:
            calls = calls[:traced[kind][0]]
        out[kind] = statistics.median(
            [(t1 - t0) * 1e3 for t0, t1, _ in calls]) if calls else None
    return out


def idle_inside(gaps, spans):
    """Seconds of the sorted ``(start, end)`` ns ``gaps`` that fall inside
    the ``(start, end)`` ns ``spans``."""
    starts = [g[0] for g in gaps]
    total = 0
    for lo, hi in spans:
        i = max(0, bisect.bisect_right(starts, lo) - 1)
        while i < len(gaps) and gaps[i][0] < hi:
            total += max(0, min(hi, gaps[i][1]) - max(lo, gaps[i][0]))
            i += 1
    return total * 1e-9


def look(trace):
    """The prefill's two waits against the prefill programs' device time,
    and the idle device time under ``apex:prefill.drain``."""
    spans = trace_reduce.host_spans(trace, "apex:")
    named = lambda n: [(s, e) for name, s, e in spans if name == "apex:" + n]
    mean_ms = lambda xs: 1e-6 * sum(e - s for s, e in xs) / len(xs) \
        if xs else None
    runs = [dur for events in trace_reduce.device_ops(
                trace, trace_reduce.MODULES_LINE).values()
            for name, _, dur, _ in events if name.startswith("jit_prefill")]
    out = {"prefill_wait_mean_ms": mean_ms(named("prefill.wait")),
           "prefill_drain_mean_ms": mean_ms(named("prefill.drain")),
           "prefill_waits": len(named("prefill.wait")),
           "prefill_drains": len(named("prefill.drain")),
           "prefill_program_runs": len(runs),
           "prefill_program_mean_ms": 1e-6 * sum(runs) / len(runs)
           if runs else None}
    window = trace_reduce.span_of(trace)
    ops = trace_reduce.device_ops(trace)
    drains = named("prefill.drain")
    if window and ops and drains:
        fullest = max(ops, key=lambda c: trace_reduce.busy_seconds(ops[c]))
        gaps = sorted(trace_reduce.idle_gaps(ops[fullest], *window))
        out["prefill_drain_s"] = 1e-9 * sum(e - s for s, e in drains)
        out["idle_under_prefill_drain_s"] = idle_inside(gaps, drains)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="where the JSON goes (default: standard error)")
    args, passed_on = ap.parse_known_args(argv)   # --rehearse, --data, ...
    got = {}
    run, load = serve.run, trace_reduce.load_xplane

    def keep_facts(ctx):
        result = run(ctx)
        got["facts"] = result["facts"]
        return result

    def keep_trace(path):
        got["trace"] = load(path)
        return got["trace"]

    serve.run, trace_reduce.load_xplane = keep_facts, keep_trace
    try:
        harness.main(["--workload", args.workload, "--seed", str(args.seed),
                      "--trace", str(args.trace), *passed_on])
    finally:
        serve.run, trace_reduce.load_xplane = run, load
    facts = got["facts"]
    at = facts["registry"]
    out = {"workload": args.workload, "seed": args.seed, "trace": args.trace,
           "phases": {"window": phases(at["open"], at["close"])},
           "stalls": stalls(at["open"], at["close"]),
           "bench_calls": bench_calls(facts)}
    if "trace_from" in at:
        out["phases"]["untraced"] = phases(at["open"], at["trace_from"])
    if "trace" in got:
        out["traced"] = look(got["trace"])
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    else:
        print(text, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of a cell through ``run.py``'s own ``main``, with what the
harness throws away kept for a look by hand at the program's spans
(``apex_tpu.observability.trace.span``, prefix ``apex:``).

    python3 benchmark/tools/program_spans.py --workload <cell> --seed 7 [--op-stats REGEX ...]
    python3 benchmark/tools/program_spans.py --workload <cell> --seed 7 --record

(``--rehearse`` and ``--data`` go on to ``run.py``.)

Default: a ``--trace 1`` run. Before ``run.py`` reduces the trace and
deletes it, this prints to ``--out``: every idle gap of the fullest chip by
the innermost ``apex:`` span over it (``readers/idle_under_span.py``), what
no such span covers, the device's runs of each program and where each
begins and ends among the spans, the medians of the calls the benchmark
timed itself, each span's count, median and summed self time, and for each ``--op-stats REGEX`` every stat
the profiler attached to the first device op that matches by its name or by
a stat, and how many ops match: what a reader could match a
``jax.named_scope`` by.

``--record``: a ``--trace 0`` run under ``span_recording()``, so the
result line's end-to-end numbers carry the in-memory buffer's cost; the
drained spans by name (count, summed and median seconds) say what set-up
spent in ``engine.build`` and the ``compile.*`` spans, which no profiler
window sees.
"""

import argparse
import glob
import json
import os
import re
import shutil
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as harness, trace_reduce  # noqa: E402
from benchmark.readers import idle_under_span, program_span  # noqa: E402

PROGRAMS = "^jit_"


def by_name(spans):
    """``{name: [seconds, ...]}`` of recorded ``Span`` tuples."""
    out = {}
    for s in spans:
        out.setdefault(s.name, []).append(s.end - s.start)
    return out


def summary(seconds):
    return {"n": len(seconds), "sum_s": sum(seconds),
            "median_ms": 1e3 * statistics.median(seconds)}


def children(spans):
    """``{span name: names seen directly or deeper inside it}``: what a
    span's self time has to leave out."""
    inside, open_ = {}, []
    for name, start, end in spans:
        open_ = [(n, e) for n, e in open_ if e > start]
        for outer, _ in open_:
            inside.setdefault(outer, set()).add(name)
        open_.append((name, end))
    return inside


def op_stats(path, patterns):
    """For each pattern, every stat of the first event of a device plane's
    ``XLA Ops`` whose name, or one of whose stats, matches it (None where
    no event does), and how many events match."""
    from jax.profiler import ProfileData
    pats = {p: re.compile(p) for p in patterns}
    first, count = dict.fromkeys(pats), dict.fromkeys(pats, 0)
    for plane in ProfileData.from_file(path).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                stats = {k: str(v) for k, v in dict(ev.stats).items()}
                for p, pat in pats.items():
                    if pat.search(ev.name) or any(
                            pat.search(v) for v in stats.values()):
                        count[p] += 1
                        if first[p] is None:
                            first[p] = {"name": ev.name[:600], "stats": {
                                k: v[:600] for k, v in stats.items()}}
    return {p: {"events": count[p], "first": first[p]} for p in pats}


def innermost(spans, instant):
    """The latest-starting span that holds ``instant``, or None."""
    holding = [sp for sp in spans if sp[1] <= instant < sp[2]]
    return max(holding, key=lambda sp: sp[1]) if holding else None


def program_edges(trace, spans):
    """Where each device program begins and ends on the host's timeline:
    ``{program: {"starts": {span: summary of ms from that span's start to
    the program's start}, "ends": {span: ms from the program's end to that
    span's end}}}``, each by the innermost ``apex:`` span holding the
    instant. A program that starts before the span that dispatches it, or
    ends after the wait for it, would show the two clocks apart."""
    out = {}
    for events in trace_reduce.device_ops(
            trace, trace_reduce.MODULES_LINE).values():
        for name, start, dur, _ in events:
            if not re.search(PROGRAMS, name):
                continue
            edges = out.setdefault(name.split("(")[0],
                                   {"starts": {}, "ends": {}})
            for key, instant in (("starts", start), ("ends", start + dur)):
                sp = innermost(spans, instant)
                edges[key].setdefault(sp[0] if sp else "(no apex span)", []) \
                    .append(abs(instant - sp[1 if key == "starts" else 2])
                            * 1e-9 if sp else 0.0)
    return {prog: {key: {n: summary(v) for n, v in by.items()}
                   for key, by in edges.items()}
            for prog, edges in out.items()}


def bench_calls(facts):
    """Medians, in ms, of the traced calls the benchmark's own wrapper
    timed (serving): what the program's spans have to agree with."""
    traced = facts.get("traced")
    if not traced or "decode" not in traced:
        return None
    out = {}
    for kind in ("decode", "prefill"):
        lo, hi = traced[kind]
        calls = facts[kind + "_calls"][lo:hi]
        if calls:
            out[kind] = summary([c[1] - c[0] for c in calls])
    return out


def look(path, pattern):
    trace = trace_reduce.load_xplane(path)
    window = trace_reduce.span_of(trace)
    modules = trace_reduce.device_ops(trace, trace_reduce.MODULES_LINE)
    programs = {}
    for events in modules.values():
        for name, _, dur, _ in events:
            if re.search(PROGRAMS, name):
                programs.setdefault(name.split("(")[0], []).append(dur * 1e-9)
    spans = trace_reduce.host_spans(trace, idle_under_span.PREFIX)
    inside = children(spans)
    names = sorted({n for n, _, _ in spans})
    out = {"window_s": (window[1] - window[0]) * 1e-9 if window else None,
           "idle_by_span_s": idle_under_span.gaps_by_span(trace),
           "program_runs": {k: summary(v) for k, v in programs.items()},
           "program_edges": program_edges(trace, spans),
           "span_total": {n: summary([(e - s) * 1e-9 for s, e in
                                      program_span.named(spans, n)])
                          for n in names},
           "span_self": {n: summary([ms * 1e-3 for ms in
                                     program_span.self_times_ms(
                                         spans, n, sorted(inside.get(n, ())))])
                         for n in names}}
    if pattern:
        out["op_stats"] = op_stats(path, pattern)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--record", action="store_true")
    ap.add_argument("--op-stats", action="append", metavar="REGEX")
    ap.add_argument("--out", default=None,
                    help="where the JSON goes (default: standard error)")
    args, passed_on = ap.parse_known_args(argv)   # --rehearse, --data
    run_args = ["--workload", args.workload, "--seed", str(args.seed),
                *passed_on]
    if args.seconds is not None:
        run_args += ["--seconds", str(args.seconds)]

    if args.record:
        from apex_tpu.observability import trace as program_trace
        with program_trace.span_recording():
            harness.main(run_args + ["--trace", "0"])
            spans = program_trace.drain_spans()
        out = {"recorded": {n: summary(v)
                            for n, v in sorted(by_name(spans).items())}}
    else:
        out, reduce = {}, harness.read_trace

        def keep_a_look(ctx, result, *rest):
            out.update(look(ctx.trace_path, args.op_stats),
                       bench_calls=bench_calls(result["facts"]))
            return reduce(ctx, result, *rest)

        harness.read_trace = keep_a_look
        try:
            harness.main(run_args + ["--trace", "1"])
        finally:
            harness.read_trace = reduce
        # a rehearsal reduces nothing, so its trace is still there
        left = glob.glob(os.path.join(harness.ROOT, ".bench_trace", "plugins",
                                      "profile", "*", "*.xplane.pb"))
        if left:
            out.update(look(left[0], args.op_stats))
            shutil.rmtree(os.path.join(harness.ROOT, ".bench_trace"))
    text = json.dumps(dict(out, workload=args.workload, seed=args.seed))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    else:
        print(text, file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files BY NAME (``workloads/<cell>.json`` -> its ``config``
and ``kind`` -> ``configs/<config>.json`` and ``kinds/<kind>.py``; the
configuration's ``family`` -> ``families/<family>.py``, which holds all the
harness knows of the architecture; with
``--trace 1`` every per-layer metric of ``BENCHMARK.json`` that lists the
cell -> ``metrics/<metric>.json`` -> ``readers/<reader>.py``), runs the
kind, and prints one JSON object as the last line of standard output.
Refuses to measure without a TPU and the chips the cell asks for.
``--rehearse`` drives the same code on whatever JAX finds (the CPU
sandbox, tiny cells) and prints no number under any metric's name: with
``--trace 1`` it lists, under ``readers_with_a_value``, the per-layer
metrics whose reader found something to read.
"""

import time

T_START = time.perf_counter()      # set-up is counted from here

import argparse                     # noqa: E402
import contextlib                   # noqa: E402
import glob                         # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import os                           # noqa: E402
import shutil                       # noqa: E402
import sys                          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SPAN = "bench:"
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class CompileCounter:
    count = 0


def load_family(config):
    """The module that knows the configuration's architecture, found by
    the name the configuration gives."""
    return importlib.import_module("benchmark.families." + config["family"])


class Context:
    """What a kind gets: the cell, its configuration and the family that
    configuration names, the run's arguments and the harness's
    instruments."""

    def __init__(self, args, cell, config, devices):
        self.cell, self.config, self.devices = cell, config, devices
        self.family = load_family(config)
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.trace_dir = os.path.join(ROOT, ".bench_trace")
        self.setup_s = None
        self.marks = []
        self.trace_path = None
        self._compile_events = []
        import jax.monitoring as monitoring
        monitoring.register_event_duration_secs_listener(
            lambda name, *a, **kw: self._compile_events.append(name)
            if name in COMPILE_EVENTS else None)

    def say(self, **fields):
        print(json.dumps(fields), flush=True)

    @contextlib.contextmanager
    def no_compiles(self):
        """Counts what compiles or traces inside the block."""
        counter = CompileCounter()
        before = len(self._compile_events)
        try:
            yield counter
        finally:
            counter.count = len(self._compile_events) - before

    def mark(self, name):
        """A point of set-up, in seconds since the process started."""
        self.marks.append((name, round(time.perf_counter() - T_START, 3)))

    def open_window(self):
        self.setup_s = time.perf_counter() - T_START
        self.marks.append(("window", round(self.setup_s, 3)))

    def span(self, name):
        if not self.trace:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(SPAN + name)

    def start_trace(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)

    def stop_trace(self):
        import jax
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        self.trace_path = found[0] if found else None

    def memory_peak_bytes(self):
        """The peak on the fullest chip, as JAX reports it; the chips'
        whole ``memory_stats()`` are kept for the readers."""
        self.memory_stats = [d.memory_stats() or {} for d in self.devices]
        self.say(setup_marks=dict(self.marks),
                 memory_stats_of_first_device=self.memory_stats[0])
        return int(max(s.get("peak_bytes_in_use", 0)
                       for s in self.memory_stats))


def load_cell(data_root, name):
    """A cell's file and its configuration's, found by name."""
    cell = load_json(data_root, "workloads", name + ".json")
    return cell, load_json(data_root, "configs", cell["config"] + ".json")


def enable_cache():
    """JAX's persistent compilation cache where the program's helper puts
    it (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``).
    That helper skips programs that compile in under a second; a run pays
    every one of them again, so the benchmark keeps them all."""
    import jax
    from apex_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir


def quiet_context(cell, config, devices, seed, seconds, rehearse=False):
    """A ``Context`` for the tools and the tests: no trace, says nothing."""
    args = argparse.Namespace(seed=seed, seconds=seconds, trace=0,
                              rehearse=rehearse)
    ctx = Context(args, cell, config, devices)
    ctx.say = lambda **fields: None
    return ctx


def metrics_of(manifest, group, cell_name, reported):
    """The manifest's metrics of ``group`` that this cell reports: those
    that list it under ``workloads``, or list nothing and move (or are) an
    end-to-end metric the cell reports."""
    out = []
    for m in manifest[group]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif group == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


def decide(checks, limits, compilations):
    """``correct`` from the numbers a kind compared (name -> (value,
    detail)) and the cell's ``limits``: every number that has a limit
    holds it, nothing compiled inside the window, and something was
    compared. A number without a limit is listed, not compared."""
    compared = {name: {"value": value, "limit": limits[name],
                       "detail": str(detail)}
                for name, (value, detail) in checks.items()
                if limits.get(name) is not None}
    not_compared = {name: value for name, (value, _) in checks.items()
                    if name not in compared}
    correct = (bool(compared) and compilations == 0
               and all(c["value"] <= c["limit"] for c in compared.values()))
    return compared, not_compared, correct


def read_trace(ctx, result, metric_entries, data_root):
    """The traced run's per-layer metrics, ``busy_s``/``window_s`` and the
    breakdown, from the trace the kind took."""
    from benchmark import trace_reduce
    trace = trace_reduce.load_xplane(ctx.trace_path) \
        if ctx.trace_path else {"planes": []}
    ops = trace_reduce.device_ops(trace)
    span = trace_reduce.span_of(trace)
    facts = dict(result["facts"], trace=trace, device_ops=ops,
                 config=ctx.config, family=ctx.family, cell=ctx.cell,
                 chips=len(ctx.devices),
                 device_kind=ctx.devices[0].device_kind,
                 memory_peak_bytes=result["memory_peak_bytes"],
                 memory_stats=ctx.memory_stats)
    metrics = {}
    for entry in metric_entries:
        spec = load_json(data_root, "metrics", entry["name"] + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        value = reader.read(facts, spec.get("params", {}))
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device, breakdown = {}, None
    if span is not None and ops:
        lo, hi = span
        busy = [trace_reduce.busy_seconds(ev, lo, hi) for ev in ops.values()]
        device = {"busy_s": sum(busy) / len(busy),
                  "window_s": (hi - lo) * 1e-9}
        fullest = max(ops, key=lambda c: trace_reduce.busy_seconds(ops[c]))
        breakdown = {
            "device_ops": trace_reduce.sum_by_name(ops[fullest]),
            "idle_gaps": trace_reduce.gaps_by_host_span(
                trace_reduce.idle_gaps(ops[fullest], lo, hi),
                trace_reduce.host_spans(trace, SPAN))}
    shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    return metrics, device, breakdown


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="drive the code path without a TPU; reports no "
                         "metric")
    ap.add_argument("--data", default=HERE,
                    help="directory holding configs/, workloads/, metrics/ "
                         "(default: benchmark/)")
    ap.add_argument("--manifest", default=os.path.join(ROOT,
                                                       "BENCHMARK.json"),
                    help="the manifest that says which per-layer metrics "
                         "the cell reports (default: BENCHMARK.json)")
    args = ap.parse_args(argv)

    manifest = load_json(args.manifest)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    cell, config = load_cell(args.data, args.workload)

    import jax
    devices = jax.devices()
    if not args.rehearse and (devices[0].platform != "tpu"
                              or len(devices) != cell["chips"]):
        sys.exit(f"benchmark: cell {args.workload!r} needs {cell['chips']} "
                 f"TPU chip(s); JAX found {len(devices)} x "
                 f"{devices[0].platform!r} ({devices[0].device_kind!r})")
    devices = devices[:cell["chips"]]
    cache_dir = enable_cache()

    ctx = Context(args, cell, config, devices)
    ctx.mark("jax_and_devices")
    ctx.say(cell=args.workload, seed=args.seed, seconds=args.seconds,
            trace=args.trace, cache_dir=cache_dir, jax=jax.__version__,
            device_kind=devices[0].device_kind, devices=len(devices))
    kind = importlib.import_module("benchmark.kinds." + cell["kind"])
    result = kind.run(ctx)

    end_to_end = dict(result["end_to_end"], setup_s=ctx.setup_s)
    compared, not_compared, correct = decide(
        result["checks"], cell["limits"], result["compilations"])

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": result["attempted"],
            "failed": result["failed"]}
    if args.trace:
        entries = metrics_of(manifest, "per_layer", args.workload,
                             set(end_to_end))
        metrics, traced_device, breakdown = read_trace(
            ctx, result, entries, args.data)
    if args.rehearse:
        line.update(metrics={}, rehearsal=True, device=device)
        if args.trace:
            line["readers_with_a_value"] = sorted(metrics)
    elif args.trace:
        device.update(traced_device)
        line.update(metrics=metrics, device=device)
        if breakdown:
            line["breakdown"] = breakdown
    else:
        entries = metrics_of(manifest, "end_to_end", args.workload, ())
        line.update(metrics={
            e["name"]: {"value": end_to_end[e["name"]], "unit": e["unit"]}
            for e in entries if e["name"] in end_to_end}, device=device)
    compared["compilations_in_window"] = {"value": result["compilations"],
                                          "limit": 0}
    for name, c in compared.items():
        print(f"compared {name}: {c['value']:.6g} limit {c['limit']:.6g}"
              f" {c.get('detail', '')}", file=sys.stderr)
    for name, value in not_compared.items():
        print(f"not compared {name}: {value:.6g}", file=sys.stderr)
    line["compared"] = dict(compared, not_compared=not_compared)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

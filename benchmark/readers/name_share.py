"""Of the device time of the leaf ops inside the runs of the program
``params["module"]``, the share spent in ops whose name matches
``params["pattern"]``, in % (``shape_share`` picks its ops by the shape of
their result, this one by name). None where the program did not run or no
op of that name ran in it: a share reads None, never 0, when it finds
nothing."""

import re

from benchmark import trace_reduce


def read(facts, params):
    trace = facts["trace"]
    if trace_reduce.program_runs(trace, params["module"]) is None:
        return None
    pattern = re.compile(params["pattern"])
    matched = everything = 0.0
    modules = trace_reduce.device_ops(trace, trace_reduce.MODULES_LINE)
    for chip, events in facts["device_ops"].items():
        runs = trace_reduce.union(trace_reduce.intervals(
            modules.get(chip, ()), params["module"]))
        for name, start, dur, scope in trace_reduce.leaves(events):
            if not trace_reduce.clip(runs, start, start + max(dur, 1.0)):
                continue
            everything += dur
            if pattern.search(f"{name} {scope}"):     # as intervals() does
                matched += dur
    return 100.0 * matched / everything if matched else None

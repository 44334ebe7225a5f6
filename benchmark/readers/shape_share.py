"""Of the device time of the leaf ops inside the runs of the program
``params["module"]``, the share spent in ops whose result has one of the
shapes ``params["shapes"]``, in %. A dimension is a number or a name that
the metric's file gives as data: a key of the configuration, a key of the
cell's engine block (``engine.num_blocks``), or a quotient of two
configuration keys (``<key>/<key>``). The serve cell's metric names the
paged KV pool as the program holds it since PR 27, ``(layers | 1, blocks,
block_size, hidden)``: the in-place row scatters of a decode step, and any
whole-pool copy that comes back. 0.0 where the program ran and no op has
such a result; None where it did not run."""

import re

from benchmark import trace_reduce

RESULT_DIMS = re.compile(r"= \(?[a-z]+\d*\[([\d,]*)\]")


def dimension(dim, config, cell):
    if isinstance(dim, int):
        return dim
    if dim.startswith("engine."):
        return int(cell["engine"][dim[len("engine."):]])
    if "/" in dim:
        over, under = dim.split("/")
        return int(config[over]) // int(config[under])
    return int(config[dim])


def result_dims(text):
    """``[36, 257, 128, 1280]`` out of ``%copy.47 = bf16[36,257,128,1280]{..}
    copy(...)``; None where the text shows no array result."""
    m = RESULT_DIMS.search(text)
    if not m:
        return None
    return [int(d) for d in m.group(1).split(",") if d]


def read(facts, params):
    trace = facts["trace"]
    if trace_reduce.program_runs(trace, params["module"]) is None:
        return None
    shapes = [[dimension(d, facts["config"], facts["cell"]) for d in shape]
              for shape in params["shapes"]]
    matched = everything = 0.0
    modules = trace_reduce.device_ops(trace, trace_reduce.MODULES_LINE)
    for chip, events in facts["device_ops"].items():
        in_run = trace_reduce.meets(trace_reduce.union(
            trace_reduce.intervals(modules.get(chip, ()), params["module"])))
        for _, start, dur, text in trace_reduce.leaves(events):
            if not in_run(start, start + max(dur, 1.0)):
                continue
            everything += dur
            if result_dims(text) in shapes:
                matched += dur
    return 100.0 * matched / everything if everything else None

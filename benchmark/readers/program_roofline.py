"""A whole program's share of its roofline: the least time the chip could
take for the work the algorithm needs in the program's runs inside the
trace, over the device time of those runs (their events in ``XLA
Modules``; mean over the chips, each doing its share of the work).

``params``: ``module`` (regex on the program's name, ``^jit_decode_step``)
and ``work``, the name of a function of the family's ``counts`` that
returns ``{"flops", "bytes"}`` of one call, ``work(config, **step)``,
``step`` being what the family kept with each traced decode call
(``step_facts``). The work is the mean over the traced calls times the runs
the device shows: the profiler can stall the first dispatch after its
start, so the host's count of the traced part may differ from the
device's (``trace_reduce.program_runs``). None where the program did not
run in the trace or no call was traced: never 0 for nothing to read."""

from benchmark import counts, trace_reduce


def read(facts, params):
    traced = facts.get("traced")
    if not traced or not facts.get("device_ops"):
        return None
    modules = trace_reduce.device_ops(facts["trace"],
                                      trace_reduce.MODULES_LINE)
    runs = [trace_reduce.intervals(events, params["module"])
            for events in modules.values()]
    runs = [iv for iv in runs if iv]
    if not runs:
        return None
    lo, hi = traced["decode"]
    calls = facts["decode_calls"][lo:hi]
    if not calls:
        return None
    needs = getattr(facts["family"].counts, params["work"])
    total = {"flops": 0.0, "bytes": 0.0}
    for _, _, step in calls:
        w = needs(facts["config"], **step)
        total = {k: total[k] + w[k] for k in total}
    n_runs = min(len(iv) for iv in runs)
    seconds = sum(trace_reduce.total(iv) for iv in runs) * 1e-9 / len(runs)
    work = {k: v * n_runs / len(calls) / facts["chips"]
            for k, v in total.items()}
    bound_s, _ = counts.roofline_seconds(
        work, counts.peaks(facts["device_kind"]))
    return 100.0 * bound_s / seconds

"""A kernel's share of its roofline: the least time the chip could take for
the work the algorithm needs, over the device time of the kernel's events
in the trace (mean over the chips; each chip does its share of the work).

``params``: ``pattern`` (regex on the event's name and scope) and ``work``,
the name of a function of the family's ``counts`` that returns ``{"flops",
"bytes"}``. With ``module`` (a step program's name in ``XLA Modules``) the
work is that of the steps the device ran inside the trace,
``work(config, rows, seq)`` over all their rows; without it, the sum of
``work(config, **step)`` over the traced decode calls, ``step`` being what
the family kept with each (``step_facts``)."""

from benchmark import counts, trace_reduce


def _work(facts, params):
    cfg, traced = facts["config"], facts["traced"]
    needs = getattr(facts["family"].counts, params["work"])
    if "module" in params:
        job = facts["cell"]["job"]
        runs = trace_reduce.program_runs(facts["trace"], params["module"])
        if runs is None:
            return None
        rows = facts["tokens_per_step"] // job["seq"] * runs[0]
        return needs(cfg, rows, job["seq"])
    lo, hi = traced["decode"]
    total = {"flops": 0.0, "bytes": 0.0}
    for _, _, step in facts["decode_calls"][lo:hi]:
        w = needs(cfg, **step)
        total = {k: total[k] + w[k] for k in total}
    return total


def read(facts, params):
    if not facts.get("traced") or not facts["device_ops"]:
        return None
    seconds = [trace_reduce.total(trace_reduce.intervals(
        events, params["pattern"])) * 1e-9
        for events in facts["device_ops"].values()]
    kernel_s = sum(seconds) / len(seconds)
    if kernel_s <= 0:
        return None                      # the kernel is not on the path
    work = _work(facts, params)
    if work is None:
        return None
    work = {k: v / facts["chips"] for k, v in work.items()}
    bound_s, _ = counts.roofline_seconds(
        work, counts.peaks(facts["device_kind"]))
    return 100.0 * bound_s / kernel_s

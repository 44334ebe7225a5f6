"""A kernel's share of its roofline where the kernel runs in more than one
of the engine's programs (the expert product runs in every decode step and
in every prefill): the least time the chip could take for the work of ALL
the traced calls named in ``params["calls"]`` (``decode_calls``,
``prefill_calls``), ``work(config, **step)`` of the family's counts summed
over them, over the device time of the events that match
``params["pattern"]``. ``kernel_roofline`` counts the decode calls alone
and would set a decode step's work against both programs' time."""

from benchmark import counts, trace_reduce


def read(facts, params):
    traced = facts.get("traced")
    if not traced or not facts["device_ops"]:
        return None
    seconds = [trace_reduce.total(trace_reduce.intervals(
        events, params["pattern"])) * 1e-9
        for events in facts["device_ops"].values()]
    kernel_s = sum(seconds) / len(seconds)
    if kernel_s <= 0:
        return None                      # the kernel is not on the path
    needs = getattr(facts["family"].counts, params["work"])
    total = {"flops": 0.0, "bytes": 0.0}
    for calls in params["calls"]:
        lo, hi = traced[calls.split("_")[0]]
        for _, _, step in facts[calls][lo:hi]:
            w = needs(facts["config"], **step)
            total = {k: total[k] + w[k] for k in total}
    work = {k: v / facts["chips"] for k, v in total.items()}
    bound_s, _ = counts.roofline_seconds(
        work, counts.peaks(facts["device_kind"]))
    return 100.0 * bound_s / kernel_s

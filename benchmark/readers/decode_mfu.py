"""The decode steps' share of the chip's peak: the FLOPs the algorithm
needs for each traced step (``decode_step_flops`` of the family's counts, of
the configuration and what the family kept with the call: its slots'
context lengths), over the steps' wall time (dispatch to host array) times
peak."""

from benchmark import counts


def read(facts, params):
    traced = facts.get("traced")
    if not traced:
        return None
    lo, hi = traced["decode"]
    calls = facts["decode_calls"][lo:hi]
    wall = sum(t1 - t0 for t0, t1, _ in calls)
    if wall <= 0:
        return None
    needs = facts["family"].counts.decode_step_flops
    flops = sum(needs(facts["config"], **step) for _, _, step in calls)
    peak = counts.peaks(facts["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (wall * peak)

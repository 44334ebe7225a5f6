"""The decode steps' share of the chip's peak: the FLOPs the algorithm
needs for each traced step at its slots' context lengths, over the steps'
wall time (dispatch to host array) times peak."""

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
    flops = sum(counts.decode_step_flops(facts["config"], contexts)
                for _, _, contexts in calls)
    peak = counts.peaks(facts["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (wall * peak)

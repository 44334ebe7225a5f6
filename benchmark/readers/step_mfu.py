"""The whole train step's share of the chips' peak: the FLOPs the algorithm
needs per token (forward + backward, no recompute; ``train_flops_per_token`` of
the family's counts) times the tokens of the steps the device
ran inside the trace, over the seconds from the first step's start to the
last one's end on the device's clock, times chips times peak.
``params["module"]``: the step program's name in ``XLA Modules``."""

from benchmark import counts, trace_reduce


def read(facts, params):
    runs = trace_reduce.program_runs(facts["trace"], params["module"])
    if runs is None:
        return None
    steps, seconds = runs
    seq = facts["cell"]["job"]["seq"]
    per_token = facts["family"].counts.train_flops_per_token(
        facts["config"], seq)
    flops = per_token * facts["tokens_per_step"] * steps
    peak = counts.peaks(facts["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (seconds * facts["chips"] * peak)

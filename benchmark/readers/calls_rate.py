"""Units a second over the traced calls the benchmark's own wrapper
recorded: the sum of ``step[params["count"]]`` (for ``prefill_calls``,
``tokens``: the prompt's own positions, never a bucket's padding) over the
sum of the calls' host-clock spans. None where no call was traced."""


def read(facts, params):
    traced = facts.get("traced")
    if not traced:
        return None
    lo, hi = traced[params["calls"].split("_")[0]]
    calls = facts[params["calls"]][lo:hi]
    seconds = sum(t1 - t0 for t0, t1, _ in calls)
    if not calls or seconds <= 0:
        return None
    return sum(step[params["count"]] for _, _, step in calls) / seconds

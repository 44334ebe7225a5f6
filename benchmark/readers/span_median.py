"""The median host-clock span, in ms, of the traced calls the benchmark's
own wrapper recorded (``params["calls"]``: ``decode_calls`` |
``prefill_calls``)."""

import statistics


def read(facts, params):
    traced = facts.get("traced")
    if not traced:
        return None
    lo, hi = traced[params["calls"].split("_")[0]]
    spans = [(c[1] - c[0]) * 1e3 for c in facts[params["calls"]][lo:hi]]
    return statistics.median(spans) if spans else None

"""The ``q``-th percentile (nearest rank) of a request latency the serve
kind times on the host clock, in ms, over the requests that FINISHED
before the traced part began: the profiler's start and stop each stall
the loop for seconds, and a request alive then would carry the stall.
``params["latency"]``: ``ttft`` (first token less the instant the request
was due) or ``tpot`` ((last token less first) / (tokens - 1), requests of
two tokens or more). None where the run was not traced or no such request
finished."""

import math

COLUMN = {"ttft": 1, "tpot": 2}


def read(facts, params):
    traced = facts.get("traced")
    rows = facts.get("latencies_ms")
    if not traced or not rows:
        return None
    col = COLUMN[params["latency"]]
    ordered = sorted(r[col] for r in rows
                     if r[0] < traced["t_from"] and r[col] is not None)
    if not ordered:
        return None
    rank = max(1, math.ceil(params.get("q", 95) / 100.0 * len(ordered)))
    return ordered[rank - 1]

"""The ``q``-th percentile of submit-to-slot wait plus the generator's
lateness, in ms, over the requests due before the traced part began (the
profiler's start and stop each stall the loop for seconds; what was due
from then on is left out)."""

import math


def read(facts, params):
    traced = facts.get("traced")
    waits = facts.get("queue_wait_ms")
    if not traced or not waits:
        return None
    ordered = sorted(w for due, w in waits if due < traced["t_from"])
    if not ordered:
        return None
    rank = max(1, math.ceil(params.get("q", 95) / 100.0 * len(ordered)))
    return ordered[rank - 1]

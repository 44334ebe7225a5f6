"""Per step, the time of the collective ops (``params["pattern"]``) during
which no other op ran on that chip, in ms; the worst chip's."""

from benchmark import trace_reduce


def read(facts, params):
    traced = facts.get("traced")
    if not traced or not traced.get("steps") or facts["chips"] < 2:
        return None
    per_chip = [trace_reduce.exposed_seconds(ev, params["pattern"])
                for ev in facts["device_ops"].values()
                if trace_reduce.intervals(ev, params["pattern"])]
    if not per_chip:
        return None
    return 1e3 * max(per_chip) / traced["steps"]

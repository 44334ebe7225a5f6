"""The share of the traced window in which no op ran on the device (the
busiest chip's, where there are several)."""

from benchmark import trace_reduce


def read(facts, params):
    span = trace_reduce.span_of(facts["trace"])
    if span is None:
        return None
    lo, hi = span
    busy = max(trace_reduce.busy_seconds(ev, lo, hi)
               for ev in facts["device_ops"].values())
    return 100.0 * (1.0 - busy / ((hi - lo) * 1e-9))

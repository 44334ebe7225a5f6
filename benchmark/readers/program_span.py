"""A span of the program's own (``apex_tpu.observability.trace.span``, on
the host plane under the name ``params["span"]``): per occurrence its self
time, the span's length less the part covered by the spans named in
``params["minus"]`` that lie inside it; the ``params["stat"]`` (``median``,
any function of ``statistics``) over the occurrences, in ms. None where
the span does not occur, as on a program that has no such span."""

import os
import statistics

from benchmark import trace_reduce


def named(spans, name):
    """``(start, end)`` of the spans called exactly ``name``."""
    return [(s, e) for n, s, e in spans if n == name]


def self_times_ms(spans, name, minus=()):
    """``spans``: ``[(name, start_ns, end_ns), ...]`` as ``host_spans``
    gives them."""
    holes = trace_reduce.union(
        [iv for other in minus for iv in named(spans, other)])
    return [(e - s - trace_reduce.total(trace_reduce.clip(holes, s, e)))
            * 1e-6 for s, e in named(spans, name)]


def read(facts, params):
    minus = params.get("minus", ())
    # one pass over the host plane for all the names
    spans = trace_reduce.host_spans(
        facts["trace"], os.path.commonprefix([params["span"], *minus]))
    values = self_times_ms(spans, params["span"], minus)
    if not values:
        return None
    return getattr(statistics, params.get("stat", "median"))(values)

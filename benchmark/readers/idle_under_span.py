"""Idle device time by what the program's host code was doing: the idle
gaps of the fullest chip inside the traced span, each put down to the
innermost ``apex:`` span that covers it; the seconds under the names in
``params["spans"]``, over the runs of the program ``params["per"]``, in
ms a run. 0.0 where the program ran and no gap fell under those names;
None where it did not run, or the trace holds no ``apex:`` span at all
(a program from before the spans)."""

from benchmark import trace_reduce

PREFIX = "apex:"
OTHER = "(no apex span)"


def gaps_by_span(trace):
    """``{span name: idle seconds}`` on the fullest chip, what no ``apex:``
    span covers under ``OTHER``; None without device ops or spans."""
    window = trace_reduce.span_of(trace)
    spans = trace_reduce.host_spans(trace, PREFIX)
    if window is None or not spans:
        return None
    ops = trace_reduce.device_ops(trace)
    fullest = max(ops, key=lambda c: trace_reduce.busy_seconds(ops[c]))
    gaps = trace_reduce.idle_gaps(ops[fullest], *window)
    # of two spans with one start the shorter is the inner one:
    # gaps_by_host_span keeps this order among equal starts
    spans.sort(key=lambda s: (s[1], s[2]))
    return dict(trace_reduce.gaps_by_host_span(gaps, spans, top=None,
                                               other=OTHER))


def read(facts, params):
    runs = trace_reduce.program_runs(facts["trace"], params["per"])
    if runs is None:
        return None
    by_span = gaps_by_span(facts["trace"])
    if by_span is None:
        return None
    seconds = sum(by_span.get(name, 0.0) for name in params["spans"])
    return 1e3 * seconds / runs[0]

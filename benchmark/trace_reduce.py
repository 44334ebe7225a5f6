"""From a profiler trace to numbers: busy union, per-name sums, idle gaps
by host span, exposed collective time.

Two halves. ``load_xplane`` turns the profiler's ``.xplane.pb`` into plain
data (``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
duration_ns, scope], ...]}]}]}``) with nothing but ``jax.profiler
.ProfileData``; everything below it is arithmetic over lists of
``(start, end)`` intervals, checked in ``tests/benchmark`` on hand-made
cases and on a small recorded trace.

What the names are on this chip (looked at by hand, PERF.md PR 25): one
plane ``/device:TPU:<n>`` per chip. Its line ``XLA Ops`` holds one event per
executed HLO op, nested under the ``while`` ops of the layer and microbatch
loops; an event's name is the whole HLO instruction, ``%flash_attention.22
= (bf16[64,1024,64]...) custom-call(...)``, so the op's own name is what
stands before `` = `` (a Pallas kernel is named after its ``name=`` or its
``jax.named_scope``; operands further right name OTHER ops, hence patterns
anchored at the start). ``XLA Modules`` holds one event per program run,
``Async XLA Ops`` the start-to-done spans of asynchronous copies and
collectives, which overlap the ops and are no busy time of their own.
``/host:CPU`` holds the benchmark's own ``TraceAnnotation`` spans on the
line ``python3``. An event here is ``[op, start_ns, duration_ns, text]``
with ``op`` that own name, ``flash_attention.22``.
"""

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# stats that say where in the program an op came from, most specific first
SCOPE_STATS = ("tf_op", "long_name", "hlo_op")
OP_NAME = re.compile(r"^%?([^\s=(]+) = ")
TEXT_KEPT = 400          # characters of an instruction kept with its event


def op_name(text):
    """``%flash_attention.22 = (...) custom-call(...)`` ->
    ``flash_attention.22``; anything else unchanged."""
    m = OP_NAME.match(text)
    return m.group(1) if m else text


def load_xplane(path):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = []
            for ev in line.events:
                stats = dict(ev.stats)
                scope = next((str(stats[k]) for k in SCOPE_STATS
                              if stats.get(k)), "")
                text = ev.name if not scope else f"{ev.name} {scope}"
                events.append([op_name(ev.name), float(ev.start_ns),
                               float(ev.duration_ns), text[:TEXT_KEPT]])
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_ops(trace, line_name=OPS_LINE):
    """``{chip index: [event, ...]}`` from each chip's ``XLA Ops`` line."""
    out = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == line_name:
                out.setdefault(int(m.group(1)), []).extend(line["events"])
    return out


def program_runs(trace, pattern):
    """How often the program whose name matches ``pattern`` ran on the
    device inside the trace, and the seconds from its first start to its
    last end, on the device's clock: ``(runs, seconds)``, or None where it
    did not run. One event of ``XLA Modules`` is one run; on several chips
    every chip runs the program once a step: the fewest runs, the longest
    span. The host's own count of the traced part is no substitute: the
    profiler can stall the first dispatch after its start for seconds, with
    nothing on the device yet (PERF.md, PR 25)."""
    per_chip = [intervals(events, pattern)
                for events in device_ops(trace, MODULES_LINE).values()]
    per_chip = [iv for iv in per_chip if iv]
    if not per_chip:
        return None
    return (min(len(iv) for iv in per_chip),
            max(max(e for _, e in iv) - min(s for s, _ in iv)
                for iv in per_chip) * 1e-9)


def host_spans(trace, prefix):
    """The benchmark's own spans (names starting with ``prefix``) from the
    host plane: ``[(name, start_ns, end_ns), ...]``."""
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur, _ in line["events"]:
                if name.startswith(prefix):
                    out.append((name, start, start + dur))
    return sorted(out, key=lambda s: s[1])


def intervals(events, pattern=None, exclude=None):
    """``(start, end)`` of the events whose op name (or, further right, its
    instruction text) matches ``pattern`` (all, if None) and not
    ``exclude``. Anchor a pattern with ``^`` to match the op's own name."""
    pat = re.compile(pattern) if pattern else None
    exc = re.compile(exclude) if exclude else None
    out = []
    for name, start, dur, scope in events:
        text = f"{name} {scope}"
        if pat is not None and not pat.search(text):
            continue
        if exc is not None and exc.search(text):
            continue
        out.append((start, start + dur))
    return out


def union(ivals):
    """Disjoint sorted intervals covering the same instants."""
    out = []
    for start, end in sorted(ivals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(ivals):
    return sum(end - start for start, end in ivals)


def clip(ivals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in ivals
            if min(e, hi) > max(s, lo)]


def meets(ivals):
    """A test ``(lo, hi) -> bool`` of whether ``clip(ivals, lo, hi)`` is
    not empty, for ``ivals`` disjoint and sorted (as ``union`` gives them),
    by bisection: a reader asks it of ~10^6 ops against ~10^3 program
    runs."""
    ivals = [(s, e) for s, e in ivals if e > s]
    ends = [e for _, e in ivals]

    def test(lo, hi):
        i = bisect.bisect_right(ends, lo)        # the first that ends past lo
        return i < len(ivals) and ivals[i][0] < hi and lo < hi

    return test


def subtract(ivals, holes):
    """The part of ``ivals`` (disjoint, sorted) outside ``holes``
    (disjoint, sorted)."""
    out = []
    for start, end in ivals:
        cur = start
        for hs, he in holes:
            if he <= cur:
                continue
            if hs >= end:
                break
            if hs > cur:
                out.append((cur, hs))
            cur = max(cur, he)
            if cur >= end:
                break
        if cur < end:
            out.append((cur, end))
    return out


def leaves(events):
    """The events that contain no other event: a ``while`` or a ``call``
    spans its body's ops and is not work of its own."""
    evs = sorted(events, key=lambda ev: (ev[1], -(ev[1] + ev[2])))
    out = []
    for i, ev in enumerate(evs):
        s, d = ev[1], ev[2]
        if i + 1 < len(evs):
            ns, nd = evs[i + 1][1], evs[i + 1][2]
            if ns < s + d and ns + nd <= s + d and (ns, nd) != (s, d):
                continue                  # has a child
        out.append(ev)
    return out


def leaf_intervals(events):
    return [(s, s + d) for _, s, d, _ in leaves(events)]


def busy_seconds(events, lo=None, hi=None):
    """Seconds in which some op ran: the union of the ops' intervals."""
    u = union(intervals(events))
    if lo is not None:
        u = clip(u, lo, hi)
    return total(u) * 1e-9


def sum_by_name(events, top=10):
    """The ``top`` ops by summed duration, each named with the shape of its
    result; leaf ops only (a loop's own event would count its body twice):
    ``[[name, seconds], ...]``."""
    sums = {}
    for name, _, d, text in leaves(events):
        key = f"{name} {_result_shape(text)}".strip()[:80]
        sums[key] = sums.get(key, 0.0) + d * 1e-9
    return [[k, v] for k, v in sorted(sums.items(),
                                      key=lambda kv: -kv[1])[:top]]


def _result_shape(text):
    """``bf16[24,4,1024,4096]`` out of an instruction's text: what tells
    one ``fusion.N`` from another in a breakdown."""
    m = re.search(r"= \(?([a-z]+\d*\[[\d,]*\])", text)
    return m.group(1) if m else ""


def idle_gaps(events, lo, hi):
    """The instants of ``[lo, hi]`` in which no op ran, as intervals."""
    return subtract([(lo, hi)], clip(union(intervals(events)), lo, hi))


def gaps_by_host_span(gaps, spans, top=10, other="(no benchmark span)"):
    """Idle seconds by the host span that covered them: each gap is split
    over the spans it overlaps, innermost (latest-starting) span first;
    what no span covers goes to ``other``. ``[[name, seconds], ...]``.

    The spans are put in that order once, and a gap looks only at those
    that start before its end, and no further than the last that can still
    reach it: a trace holds ~10^5 gaps and ~10^4 spans."""
    order = sorted(spans, key=lambda s: -s[1])
    neg_starts = [-ss for _, ss, _ in order]
    # reach[j]: the latest end of order[j:], the spans that start no later
    reach = [0.0] * (len(order) + 1)
    reach[len(order)] = float("-inf")
    for j in range(len(order) - 1, -1, -1):
        reach[j] = max(order[j][2], reach[j + 1])
    sums = {}
    for gs, ge in gaps:
        rest = [(gs, ge)]
        j = bisect.bisect_right(neg_starts, -ge)     # first start below ge
        while rest and j < len(order) and reach[j] > gs:
            name, ss, se = order[j]
            j += 1
            if se <= gs:
                continue
            covered = clip(rest, ss, se)
            if covered:
                sums[name] = sums.get(name, 0.0) + total(covered) * 1e-9
                rest = subtract(rest, union(covered))
        if rest:
            sums[other] = sums.get(other, 0.0) + total(rest) * 1e-9
    return [[k, v] for k, v in sorted(sums.items(),
                                      key=lambda kv: -kv[1])[:top]]


def exposed_seconds(events, pattern):
    """Seconds of the ops matching ``pattern`` (collectives) during which
    no other leaf op ran on the same chip."""
    mine = union(intervals(events, pattern))
    others = union(leaf_intervals(
        [ev for ev in events
         if not re.search(pattern, f"{ev[0]} {ev[3]}")]))
    return total(subtract(mine, others)) * 1e-9


def span_of(trace):
    """First start and last end over every device op: the traced window on
    the device's clock."""
    starts, ends = [], []
    for events in device_ops(trace).values():
        for _, s, d, _ in events:
            starts.append(s)
            ends.append(s + d)
    if not starts:
        return None
    return min(starts), max(ends)

"""A number from the program's own counters and histograms
(``facts["registry"]``) over the UNTRACED first part of a traced run: the
growth from ``open`` to ``trace_from``. Nothing past ``trace_from`` is
read: the profiler's start stalls the first dispatch after it for seconds
(PR 25), inside a timed span, which would swamp any sum over the window.

``params``: ``over`` and ``under``, each ``{name: weight}``; the value is
``scale`` x (sum of weight x growth over ``over``) / (the same over
``under``). A histogram's names are its ``_sum`` and ``_count``
(``serve/span_ms/decode.wait_sum``).

None where a name is missing at either point (a program from before it),
the run was not traced, or the denominator did not grow: never 0 for
nothing to read."""


def read(facts, params):
    registry = facts.get("registry") or {}
    lo, hi = registry.get("open"), registry.get("trace_from")
    if lo is None or hi is None:
        return None

    def grown(terms):
        if any(name not in lo or name not in hi for name in terms):
            return None
        return sum(w * (hi[name] - lo[name]) for name, w in terms.items())

    over, under = grown(params["over"]), grown(params["under"])
    if over is None or under is None or under <= 0:
        return None
    return params.get("scale", 1.0) * over / under

"""A number from the program's own counters (``facts["registry"]``: the
scheduler's ``MetricsRegistry`` as it stood at ``open``, ``trace_from``,
``trace_to`` and ``close``), over the window (``close`` less ``open``).
``params["stat"]``:

- ``ratio``: the growth of counter ``over`` by the growth of counter
  ``under``, times ``scale``;
- ``max_over_mean``: over the counters whose name starts with ``prefix``,
  the largest growth by the mean growth (1.0: an even spread).

None where the program has no such counter (a program from before it) or
nothing was counted: never 0 for nothing to read."""


def growth(registry, name):
    return registry["close"].get(name, 0.0) - registry["open"].get(name, 0.0)


def read(facts, params):
    registry = facts.get("registry") or {}
    if "open" not in registry or "close" not in registry:
        return None
    if params["stat"] == "ratio":
        if params["under"] not in registry["close"]:
            return None
        under = growth(registry, params["under"])
        if under <= 0:
            return None
        return params.get("scale", 1.0) * growth(
            registry, params["over"]) / under
    if params["stat"] == "max_over_mean":
        grown = [growth(registry, name) for name in registry["close"]
                 if name.startswith(params["prefix"])]
        if not grown or sum(grown) <= 0:
            return None
        return max(grown) * len(grown) / sum(grown)
    raise ValueError(f"unknown stat {params['stat']!r}")

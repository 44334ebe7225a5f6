"""A gauge of the program's own counters (``facts["registry"]``) as it
stood when the window closed, times ``params["scale"]``
(``params["name"]``: the gauge's name). For a gauge that keeps the most it
has seen (a high-water mark), that is the most of the whole run. None where
the program has no such gauge (a program from before it): never 0 for
nothing to read."""


def read(facts, params):
    close = (facts.get("registry") or {}).get("close") or {}
    if params["name"] not in close:
        return None
    return params.get("scale", 1.0) * close[params["name"]]

"""Device memory at the close of the window, in GB, on the fullest chip:
the buffers in use then (state, batch) plus the most the runtime has held
reserved for the programs' temporaries. ``peak_bytes_in_use`` alone leaves
the temporaries out on this runtime (PERF.md, PR 25) and is raised by the
benchmark's own check readings before the window; where the runtime
reports no reservation, it is what is left to read."""


def read(facts, params):
    stats = [s for s in facts.get("memory_stats") or [] if s]
    if not stats:
        return None
    full = max(s["bytes_in_use"] + s["peak_bytes_reserved"]
               if "peak_bytes_reserved" in s else s["peak_bytes_in_use"]
               for s in stats)
    return full / 1e9

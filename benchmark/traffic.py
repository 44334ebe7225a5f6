"""The one general generator: a cell's traffic or job from its parameters
and the seed.

Every seed gets the SAME multiset of sizes and arrival gaps, in another
order: sizes are the distribution's own quantiles (not draws), and the seed
only permutes them and draws the token ids. Runs with different seeds then
do the same amount of work, and differ only in how it is interleaved. The
gaps are the exponential's quantiles, so the arrivals are a LOW-VARIANCE
STAND-IN for a Poisson process, not draws from one: the count in the
window is fixed, and with ``shuffle_block`` no burst and no run of long
requests can form (PERF.md section 4 sets true Poisson draws beside it).

Serving parameters (``traffic_params`` in a workload file):

    {"rate_per_s": 10.0,
     "shuffle_block": 8,                              optional, see arrange
     "prompt_len": {"median": 128, "sigma": 0.8, "min": 16, "max": 512},
     "output_len": {"median": 64, "sigma": 0.7, "min": 8, "max": 256}}

Training parameters (``job``): ``microbatches``, ``micro_batch``, ``dp``,
``seq``; a batch is ``(microbatches, micro_batch * dp, seq)`` token ids,
the targets the same rows shifted by one.
"""

import dataclasses
import math
import statistics
from typing import List

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Arrival:
    index: int
    due_s: float            # seconds after the window opens
    prompt: List[int]
    max_new_tokens: int


def _rng(seed, stream):
    return np.random.default_rng([int(seed) & 0xFFFFFFFF,
                                  (int(seed) >> 32) & 0xFFFFFFFF, stream])


def lognormal_quantiles(spec, n):
    """``n`` lengths at the mid-quantiles of a clipped log-normal."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(x)) for x in u])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def arrival_gaps(traffic, n):
    """``n`` gaps whose sum is ``n / rate``: the exponential's
    mid-quantiles, rescaled so that every seed's last request is due at the
    same instant."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    return gaps * (n / traffic["rate_per_s"]) / gaps.sum()


def arrange(rng, values, block=None):
    """``values`` in an order drawn from ``rng``. With ``block``, the order
    is stratified: the sorted values are cut into ``block`` strata, and
    every run of ``block`` consecutive places gets one value of each
    stratum, so that any stretch of the window carries the whole
    distribution and about the same work. Which value of a stratum goes to
    which run, and the order inside a run, are drawn from ``rng``."""
    values = np.sort(np.asarray(values))
    if not block or block >= len(values):
        return rng.permutation(values)
    runs = -(-len(values) // block)
    strata = [rng.permutation(values[k * runs:(k + 1) * runs])
              for k in range(block)]
    out = [rng.permutation([s[r] for s in strata if r < len(s)])
           for r in range(runs)]
    return np.concatenate(out)


def serve_arrivals(traffic, vocab_size, seed, seconds):
    """The requests due inside a window of ``seconds``, sorted by due
    time."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    order = _rng(seed, 1)
    block = traffic.get("shuffle_block")
    gaps = arrange(order, arrival_gaps(traffic, n), block)
    # the first request is due at 0: the window opens on an arrival
    due = (np.cumsum(gaps) - gaps) * (seconds / (n / traffic["rate_per_s"]))
    prompts = arrange(order, lognormal_quantiles(traffic["prompt_len"], n),
                      block)
    outputs = arrange(order, lognormal_quantiles(traffic["output_len"], n),
                      block)
    ids = _rng(seed, 2)
    return [Arrival(i, float(due[i]),
                    ids.integers(1, vocab_size, int(prompts[i])).tolist(),
                    int(outputs[i]))
            for i in range(n)]


def train_batch(job, vocab_size, seed, step):
    """The batch of step ``step`` (0-based): rows that all differ, made on
    the host from the seed."""
    rng = _rng(seed, 1000 + step)
    shape = (job["microbatches"], job["micro_batch"] * job["dp"],
             job["seq"] + 1)
    rows = rng.integers(0, vocab_size, shape, dtype=np.int32)
    return (np.ascontiguousarray(rows[..., :-1]),
            np.ascontiguousarray(rows[..., 1:]))

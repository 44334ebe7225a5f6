"""The traffic generator: the same work for every seed, in another order."""

import collections

import numpy as np

from benchmark import traffic

MIX = {"rate_per_s": 10.0,
       "prompt_len": {"median": 128, "sigma": 0.8, "min": 16, "max": 512},
       "output_len": {"median": 64, "sigma": 0.7, "min": 8, "max": 256}}


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = traffic.serve_arrivals(MIX, 50257, 1, 30.0)
    b = traffic.serve_arrivals(MIX, 50257, 2 ** 31 + 12345, 30.0)
    assert len(a) == len(b) == 300
    for field in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert collections.Counter(map(field, a)) == \
            collections.Counter(map(field, b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    gaps = lambda rs: sorted(np.round(
        np.diff([r.due_s for r in rs] + [30.0]), 9))
    assert gaps(a) == gaps(b) and a[0].due_s == b[0].due_s == 0.0
    assert a[0].prompt != b[0].prompt
    # the same seed gives the same requests
    again = traffic.serve_arrivals(MIX, 50257, 1, 30.0)
    assert [(r.due_s, r.prompt) for r in a] == \
        [(r.due_s, r.prompt) for r in again]


def test_a_stratified_order_spreads_the_work_evenly_over_the_window():
    """With ``shuffle_block`` every stretch of the window carries about the
    same output tokens, whatever the seed; the sizes stay the mix's."""
    def thirds(mix, seed):
        rs = traffic.serve_arrivals(mix, 50257, seed, 30.0)
        return [sum(r.max_new_tokens for r in rs if lo <= r.due_s < lo + 10)
                for lo in (0, 10, 20)]
    plain = traffic.serve_arrivals(MIX, 50257, 5, 30.0)
    strat = traffic.serve_arrivals(dict(MIX, shuffle_block=8), 50257, 5, 30.0)
    assert collections.Counter(r.max_new_tokens for r in plain) == \
        collections.Counter(r.max_new_tokens for r in strat)
    spread = lambda mix: max(
        (max(t) - min(t)) / (sum(t) / 3)
        for t in (thirds(mix, seed) for seed in range(20, 32)))
    assert spread(dict(MIX, shuffle_block=8)) < 0.5 * spread(MIX)


def test_sizes_follow_the_mix_and_are_due_inside_the_window():
    rs = traffic.serve_arrivals(MIX, 50257, 7, 30.0)
    prompts = [len(r.prompt) for r in rs]
    outs = [r.max_new_tokens for r in rs]
    assert min(prompts) >= 16 and max(prompts) <= 512
    assert min(outs) >= 8 and max(outs) <= 256
    assert 110 <= np.median(prompts) <= 146
    assert 56 <= np.median(outs) <= 72
    due = [r.due_s for r in rs]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 30.0
    assert all(1 <= t < 50257 for r in rs for t in r.prompt)


def test_the_tools_true_poisson_draws_vary_where_the_stand_in_does_not():
    """``tools/poisson_tails.py`` sets i.i.d. draws beside the stand-in:
    the count and the work change with the seed, the clipping and the rate
    are the mix's."""
    from benchmark.tools import poisson_tails
    runs = [poisson_tails.iid_arrivals(MIX, 50257, seed, 30.0)
            for seed in (1, 2, 2 ** 31 + 3, 2 ** 32 + 4)]
    assert len({len(rs) for rs in runs}) > 1
    assert len({sum(r.max_new_tokens for r in rs) for rs in runs}) == 4
    for rs in runs:
        assert 220 <= len(rs) <= 380 and rs[0].due_s == 0.0
        due = [r.due_s for r in rs]
        assert due == sorted(due) and due[-1] < 30.0
        assert all(16 <= len(r.prompt) <= 512 and 8 <= r.max_new_tokens <= 256
                   for r in rs)
        # an exponential's gaps: their deviation is about their mean
        gaps = np.diff(due)
        assert 0.7 < gaps.std() / gaps.mean() < 1.4


def test_train_batches_differ_by_step_and_row_and_shift_by_one():
    job = {"microbatches": 2, "micro_batch": 2, "dp": 2, "seq": 16}
    t0, y0 = traffic.train_batch(job, 257, 2 ** 31 + 5, 0)
    t1, _ = traffic.train_batch(job, 257, 2 ** 31 + 5, 1)
    assert t0.shape == y0.shape == (2, 4, 16) and t0.dtype == np.int32
    assert (t0[..., 1:] == y0[..., :-1]).all()
    assert not (t0 == t1).all()
    rows = t0.reshape(-1, 16)
    assert len({tuple(r) for r in rows}) == len(rows)
    again, _ = traffic.train_batch(job, 257, 2 ** 31 + 5, 0)
    assert (again == t0).all()

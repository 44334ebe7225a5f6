"""``benchmark/counts.py`` against ``bench.py``'s formula and hand counts."""

import json
import os

import pytest

from benchmark import counts, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,published", [("gpt2-medium", 354_823_168),
                                            ("gpt2-large", 774_030_080)])
def test_n_params_is_the_published_count(name, published):
    cfg = config(name)
    assert counts.n_params(cfg) == published
    shapes = reference.weight_shapes(cfg)
    n = 0
    for shape in shapes.values():
        size = 1
        for d in shape:
            size *= d
        n += size
    assert n == published          # the reference holds the same tensors


@pytest.mark.parametrize("name", ["gpt2-medium", "gpt2-large"])
def test_train_flops_against_bench_py(name):
    """``bench.py``: ``6 N + 12 L d seq`` per token. Ours leaves out the
    position table (a lookup) and the non-causal half of the square."""
    cfg, seq = config(name), 1024
    n, L, h = counts.n_params(cfg), cfg["n_layer"], cfg["n_embd"]
    bench_py = 6.0 * n + 12.0 * L * h * seq
    ours = counts.train_flops_per_token(cfg, seq)
    assert ours == pytest.approx(
        bench_py - 6.0 * cfg["n_positions"] * h - 6.0 * L * h * seq)
    assert 0.9 < ours / bench_py < 1.0
    # the flash kernel's share is the attention term of the same count
    rows = 16
    work = counts.flash_train_work(cfg, rows, seq)
    assert work["flops"] == pytest.approx(6.0 * L * h * seq * rows * seq)


def test_decode_step_hand_count():
    """One slot, context 300, by hand: per layer qkv 2*h*3h, proj 2*h*h,
    mlp 2*2*h*4h, attention 4*h*300; the head 2*h*V."""
    cfg = config("gpt2-large")
    h, L, V = 1280, 36, 50257
    by_hand = L * (2 * h * 3 * h + 2 * h * h + 4 * h * 4 * h
                   + 4 * h * 300) + 2 * h * V
    got = counts.decode_step_flops(cfg, [300])
    # ours also counts the biases and norms (2 FLOPs a parameter): < 0.1%
    assert by_hand <= got <= by_hand * 1.001
    two = counts.decode_step_flops(cfg, [300, 100])
    assert two - got == pytest.approx(
        counts.decode_step_flops(cfg, [100]))
    work = counts.paged_decode_work(cfg, [300, 100])
    assert work["bytes"] == 2 * L * h * 400 * 2      # K and V, bf16
    assert work["flops"] == 4 * L * h * 400


def test_roofline_says_which_bound():
    peak = counts.peaks("TPU v5 lite")
    assert peak["bf16_flops_per_s"] == 197e12
    assert peak["hbm_bytes_per_s"] == 819e9
    t, bound = counts.roofline_seconds({"flops": 197e12, "bytes": 1.0}, peak)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = counts.roofline_seconds({"flops": 1.0, "bytes": 819e9}, peak)
    assert (t, bound) == (pytest.approx(1.0), "memory")
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")

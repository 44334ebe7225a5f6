"""Family ``jamba`` in the harness, end to end on the CPU at the tiny cell's
size (``cells/workloads/tiny-jamba.chat.json``): a sound run is correct,
the fp8 control and every planted fault in the engine's place are not, the
family's counts follow the published arithmetic, and the manifest's entries
for the cell are what their files say. Entries are found by NAME, never by
their place in a list: the next cell is appended after this one."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.families import jamba as family
from benchmark.kinds import serve
from benchmark.tools import calibrate

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells")
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = "tiny-jamba.chat"
PUBLISHED = "jamba2-3b"
CELL = PUBLISHED + ".chat-r80"
SOURCE = "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
NEW_METRICS = ("mamba1_scan_roofline.serve",
               "mamba1_state_share_of_decode.serve",
               "mamba1_decode_update_roofline.serve",
               "prefill_real_token_share.serve")


def context(seed):
    cell, config = harness.load_cell(CELLS, TINY)
    return harness.quiet_context(cell, config, jax.devices()[:1], seed, 1.0,
                                 rehearse=True)


def test_sound_run_is_correct_and_reports_no_metric(capsys):
    capsys.readouterr()
    harness.main(["--workload", TINY, "--data", CELLS, "--rehearse",
                  "--seed", str(3_000_000_019), "--seconds", "1.0"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] > 0


@pytest.fixture(scope="module")
def one_seed():
    ctx = context(2 ** 31 + 13)
    return ctx, calibrate.serve_seed(ctx, controls=True)


def test_the_fp8_control_in_the_engines_place_is_not_correct(one_seed):
    ctx, got = one_seed
    limits = ctx.cell["limits"]
    program = {k: tuple(v) for k, v in got["program"].items()}
    assert harness.decide(program, limits, 0)[2], got
    control = {k[len("control_"):]: tuple(v)
               for k, v in got["control_fp8"].items()}
    compared, _, correct = harness.decide(control, limits, 0)
    assert not correct
    assert compared["token_gap_mean"]["value"] \
        > compared["token_gap_mean"]["limit"]


# the state rounded to bfloat16 every token moves the tiny cell's logits by
# 8e-3 (tests/test_jamba.py holds the program to 3e-4 and sees it) and no
# served token: bfloat16 activations move them more, here as on the chip
# (PERF.md section 2). The cell's limit sees the other five.
SEEN_BY_THE_LIMIT = tuple(f for f in family.reference.FAULTS
                          if f != "state_bf16")


@pytest.mark.parametrize("fault", SEEN_BY_THE_LIMIT)
def test_a_planted_fault_in_the_engines_place_is_not_correct(fault):
    """What the program would serve with the fault in it (the token the
    faulty forward puts first at every scored position) fails the cell's
    limit."""
    ctx = context(12)
    cfg, traffic = ctx.config, ctx.cell["traffic_params"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg["vocab_size"], n).tolist()
               for n in (traffic["prompt_len"]["max"], 13, 5)]
    streams = [rng.integers(1, cfg["vocab_size"], 12).tolist()
               for _ in prompts]
    got = serve.score(ctx, prompts, streams, control=fault)
    compared, _, correct = harness.decide(
        {"token_gap_mean": got["control_token_gap_mean"]},
        ctx.cell["limits"], 0)
    assert not correct, (fault, compared)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("jamba_reference.py", "jamba_counts.py"):
        with open(os.path.join(ROOT, "benchmark", "families", name)) as f:
            text = f.read()
        assert "apex_tpu" not in text.replace("``apex_tpu``", ""), name
    ref = open(os.path.join(ROOT, "benchmark", "families",
                            "jamba_reference.py")).read()
    # the recurrence token by token: no chunk, no associative scan
    assert "jax.lax.scan(step" in ref and "associative_scan" not in ref
    assert set(family.reference.FAULTS) == {
        "norms_left_out", "conv_tail_dropped", "skip_left_out",
        "state_bf16", "rotary_on_attention", "gate_before_skip"}


def test_the_init_is_the_mamba_papers():
    ref = family.reference
    ctx = context(12)
    lo, hi = ref.seed_key(7)
    w = jax.jit(lambda lo, hi: ref.make_weights(ctx.config, lo, hi))(lo, hi)
    lp = w["layers"][ref.MAMBA]
    assert all(v.dtype == jax.numpy.bfloat16
               for v in jax.tree_util.tree_leaves(w))
    # A = -(1..16) a channel, state index first; a skip of one
    np.testing.assert_allclose(
        np.exp(np.asarray(lp["A_log"], np.float32))[0, :, 5],
        np.arange(1, 17), rtol=1e-2)
    assert float(lp["D"].astype(np.float32).min()) == 1.0
    # dt_bias: the inverse softplus of a log-uniform draw in [1e-3, 1e-1]
    dt = np.log1p(np.exp(np.asarray(lp["dt_bias"], np.float32)))
    assert 0.9e-3 < dt.min() and dt.max() < 1.1e-1
    assert np.median(dt) < 2e-2                  # log-uniform, not uniform


# -- counts -------------------------------------------------------------------

def published():
    return harness.load_json(ROOT, "benchmark", "configs",
                             PUBLISHED + ".json")


def test_the_published_configuration_is_whole_and_is_the_issues_arithmetic():
    cfg = published()
    counts = family.counts
    assert cfg["reduced"] == [] and cfg["source"] == SOURCE
    shapes = family.reference.weight_shapes(cfg)["layers"]
    per = {k: {n: int(np.prod(s[1:])) for n, s in v.items()}
           for k, v in shapes.items()}
    mlp = ("mlp_gate", "mlp_up", "mlp_down")
    norms = ("norm", "ff_norm")
    for kind, mixer in (("mamba_mlp", 41_241_792),
                        ("attention_mlp", 13_762_560)):
        layer = per[kind]
        assert sum(layer[n] for n in mlp) == 62_914_560
        assert sum(layer[n] for n in norms) == 5_120
        assert sum(v for n, v in layer.items()
                   if n not in mlp + norms) == mixer
    assert family.reference.kinds(cfg) == {"mamba_mlp": 26,
                                           "attention_mlp": 2}
    assert counts.n_params(cfg) == 3_029_337_472 \
        == 26 * (41_241_792 + 62_914_560 + 5_120) \
        + 2 * (13_762_560 + 62_914_560 + 5_120) + 167_772_160 + 2_560
    assert counts.weight_bytes(cfg) == 6_058_674_944          # 6.06 GB
    # a slot: 26 x (16 x 5120 float32 = 327,680 B) = 8.52 MB of state and
    # 26 x 3 x 5120 bfloat16 = 0.80 MB of conv tails; 128 slots 1.19 GB
    assert counts.state_bytes_per_slot(cfg) == 26 * 327_680 + 26 * 30_720
    assert 1.19e9 < 128 * counts.state_bytes_per_slot(cfg) < 1.20e9
    # KV: 1,024 B a position over the two attention layers
    assert counts.block_bytes(cfg, 128) == 128 * 1024
    # the catalog's keys as published
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["mamba_expand"],
            cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"],
            cfg["vocab_size"], cfg["attn_layer_offset"],
            cfg["attn_layer_period"], cfg["num_experts"],
            cfg["rms_norm_eps"], cfg["tie_word_embeddings"]) \
        == (2560, 8192, 28, 20, 1, 2, 16, 4, 160, 65536, 7, 14, 1, 1e-6,
            True)
    assert family.reference.sizes(cfg) == (5120, 16, 160, 128)


def test_the_kernels_work_counts_a_prompts_own_tokens_and_the_live_slots():
    cfg = published()
    counts = family.counts
    one = counts.selective_scan_work(cfg, tokens=192)
    entries = 26 * 16 * 5120
    assert one["flops"] == 7.0 * entries * 192
    # delta, delta u and y of 5,120 channels, B and C of 16, float32, a
    # token a layer; the state once a call
    assert one["bytes"] == 26 * (192 * (3 * 5120 + 32) * 4 + 16 * 5120 * 4)
    assert counts.selective_scan_work(cfg, [5, 6]) == {
        "flops": 0.0, "bytes": 0.0}          # a decode step runs no scan
    # the bytes set the roofline: ~75 ns a token a layer on a v5e
    from benchmark import counts as chip
    bound, _ = chip.roofline_seconds(one, chip.peaks("TPU v5 lite"))
    assert 60e-9 < bound / (26 * 192) < 90e-9
    step = counts.decode_update_work(cfg, [100] * 70)
    assert step["bytes"] == 70 * entries * 4 * 2     # read once, written once
    assert counts.paged_decode_work(cfg, [1000])["bytes"] \
        == 2 * 2 * 128 * 1000 * 2                    # ONE KV head, 2 layers
    # ISSUE 38: a prefill of 192 tokens is ~1.2 TFLOP of products
    assert 1.0e12 < counts.prefill_flops(cfg, 192) < 1.3e12
    assert counts.decode_step_flops(cfg, [200] * 64) > 64 * 5.9e9


# -- the manifest: what the cell is judged on ---------------------------------

def _named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_the_cell_reports_what_its_entries_say():
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    reported = {m["name"] for m in harness.metrics_of(
        manifest, "end_to_end", CELL, ())}
    assert {"serve_tokens_per_s", "setup_s"} <= reported
    assert reported <= {"serve_tokens_per_s", "setup_s", "ttft_p95_ms",
                        "tpot_p95_ms"}
    per_layer = harness.metrics_of(manifest, "per_layer", CELL, reported)
    names = [m["name"] for m in per_layer]
    assert set(NEW_METRICS) <= set(names)
    assert "prefill_tokens_per_s.serve" in names
    assert "expert_load_max_over_mean.serve" not in names   # no experts
    for m in per_layer:
        assert m["moves"] in reported, m
        spec = harness.load_json(ROOT, "benchmark", "metrics",
                                 m["name"] + ".json")
        assert (spec["name"], spec["moves"], spec["layer"], spec["unit"]) \
            == (m["name"], m["moves"], m["layer"], m["unit"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    for name in NEW_METRICS:
        m = _named(manifest["per_layer"], name)
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["moves"] == "serve_tokens_per_s"
    entry = _named(manifest["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (PUBLISHED, "chat-r80", 1)
    assert len(entry["why"]) <= 200
    cfg_entry = _named(manifest["configs"], PUBLISHED)
    assert cfg_entry["reduced"] == [] and cfg_entry["source"] == SOURCE
    assert cfg_entry["file"] == "benchmark/configs/jamba2-3b.json"


def test_the_cell_is_the_issues_engine_and_traffic():
    cell = harness.load_json(ROOT, "benchmark", "workloads", CELL + ".json")
    eng, mix = cell["engine"], cell["traffic_params"]
    assert (eng["max_seqs"], eng["max_len"], eng["block_size"],
            eng["prefill_buckets"], eng["speculate_k"], eng["cache_dtype"]) \
        == (128, 1280, 128, [128, 256, 512, 1024], 0, "bfloat16")
    # the pool holds every slot's whole max_len, and the null block
    assert eng["num_blocks"] == {"attention_mlp": 128 * 10 + 1}
    assert mix["prompt_len"] == {"median": 192, "sigma": 0.8, "min": 32,
                                 "max": 1024}
    assert mix["output_len"] == {"median": 96, "sigma": 0.7, "min": 16,
                                 "max": 256}
    assert mix["shuffle_block"] in (8, 16) and "burst" not in json.dumps(mix)
    assert cell["check"]["requests"] == 16
    assert set(serve.REQUIRED_LIMITS) <= set(cell["limits"])
    assert cell["limits"]["short_streams"] == cell["limits"]["unfinished"] \
        == cell["limits"]["xla_attention_programs"] == 0
    # over a thousand short requests a window
    assert mix["rate_per_s"] * manifest_seconds() > 1000


def manifest_seconds():
    return harness.load_json(ROOT, "BENCHMARK.json")["run_seconds"]


def test_a_traced_rehearsal_finds_something_for_every_counter_metric(capsys):
    """The program-counter metric reads on the CPU too (the device-trace
    ones need the chip): ``serve/prefill_bucket_tokens`` is counted."""
    from benchmark.readers import registry_counters
    spec = harness.load_json(ROOT, "benchmark", "metrics",
                             "prefill_real_token_share.serve.json")
    ctx = context(5)
    server = serve.Server(ctx)
    from benchmark import traffic
    arrivals = traffic.serve_arrivals(
        ctx.cell["traffic_params"], ctx.family.vocab(ctx.config), 5, 1.0)
    server.snapshot("open")
    serve.serve_window(ctx, server, arrivals, 1.0)
    value = registry_counters.read({"registry": server.registry_at},
                                   spec["params"])
    assert value is not None and 0.0 < value <= 100.0
    # a program from before the counter: nothing to read, and no raise
    old = {k: {n: v for n, v in snap.items()
               if n != "serve/prefill_bucket_tokens"}
           for k, snap in server.registry_at.items()}
    assert registry_counters.read({"registry": old}, spec["params"]) is None

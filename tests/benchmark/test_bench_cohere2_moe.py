"""Family ``cohere2_moe`` in the harness, end to end on the CPU at the tiny
cell's size (``cells/workloads/tiny-cohere2-moe.rag.json``): a sound run is
correct, the fp8 control and every planted fault in the engine's place are
not, and the family's counts follow the routing."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.families import cohere2_moe as family
from benchmark.kinds import serve
from benchmark.readers import calls_rate, registry_counters
from benchmark.tools import calibrate

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells")
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "tiny-cohere2-moe.rag"
PUBLISHED = "command-a-plus-05-2026"


def context(seed):
    cell, config = harness.load_cell(CELLS, CELL)
    return harness.quiet_context(cell, config, jax.devices()[:1], seed, 1.0,
                                 rehearse=True)


def test_sound_run_is_correct_and_reports_no_metric(capsys):
    capsys.readouterr()
    harness.main(["--workload", CELL, "--data", CELLS, "--rehearse",
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


@pytest.mark.parametrize("fault", family.reference.FAULTS)
def test_a_planted_fault_in_the_engines_place_is_not_correct(fault):
    """What the program would serve with the fault in it (the token the
    faulty forward puts first at every scored position) fails the cell's
    limit."""
    ctx = context(12)
    cfg, traffic = ctx.config, ctx.cell["traffic_params"]
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg["vocab_size"], n).tolist()
               for n in (traffic["prompt_len"]["max"], 40, 24)]
    streams = [rng.integers(1, cfg["vocab_size"], 8).tolist()
               for _ in prompts]
    got = serve.score(ctx, prompts, streams, control=fault)
    compared, _, correct = harness.decide(
        {"token_gap_mean": got["control_token_gap_mean"]},
        ctx.cell["limits"], 0)
    assert not correct, (fault, compared)


# -- counts ---------------------------------------------------------------------

def published():
    return harness.load_json(ROOT, "benchmark", "configs",
                             PUBLISHED + ".json")


def test_the_published_cut_is_the_issues_arithmetic():
    cfg = published()
    counts = family.counts
    # 16 held + 4 shared experts of 3 x 4096 x 4096, attention 17.8 M,
    # router 0.5 M a layer; 32,768 rows of the embedding: 8.47 GB bf16
    assert 8.45e9 < counts.weight_bytes(cfg) < 8.50e9
    assert counts.block_bytes(cfg, "full_attention", 128) == 2 * 128 * 128 * 2
    assert counts.block_bytes(cfg, "sliding_attention", 128) \
        == 3 * 2 * 128 * 128 * 2
    filled = counts.blocks_filled(cfg, [8192, 8300, 100], 128)
    assert filled["full_attention"] == 64 + 65 + 1
    assert filled["sliding_attention"] == 32 + 33 + 1


def test_the_expert_products_work_follows_the_assignments():
    """At a prefill of 2,048 tokens the counted FLOPs are the assignments'
    (6 x 4096 x 4096 each), not experts x tokens: within 1.3x of the routed
    and shared rows, sixteen times under every expert over every token."""
    cfg = published()
    L, nh = cfg["num_hidden_layers"], len(cfg["held_experts"])
    rng = np.random.default_rng(0)
    picks = rng.random((L, 2048, cfg["router_width"])).argsort(-1)[
        ..., :cfg["num_experts_per_tok"]]
    load = np.stack([[(picks[l] == e).sum() for e in range(nh)]
                     for l in range(L)])
    stats = np.concatenate([load, np.zeros((L, 1), int)], axis=1).tolist()
    work = family.counts.moe_experts_work(cfg, [], tokens=2048,
                                          expert_stats=stats)
    assignments = load.sum() + L * cfg["num_shared_experts"] * 2048
    per = 6.0 * cfg["hidden_size"] * cfg["intermediate_size"]
    assert assignments * per <= work["flops"] <= 1.3 * assignments * per
    every = L * (nh + cfg["num_shared_experts"]) * 2048 * per
    assert work["flops"] < every / 3
    # a decode step reads the touched experts and no other
    step = [[3, 0, 1] + [0] * 13 + [0]] * L
    few = family.counts.moe_experts_work(cfg, [100, 200, 300, 5000],
                                         expert_stats=step)
    # (an expert's matrices are 3 x 4096 x 4096 bf16 = `per` bytes)
    assert L * (2 + 4) * per <= few["bytes"] < L * (2 + 4 + 0.01) * per
    # with no routing kept (the run's last call) the even spread stands in
    even = family.counts.moe_experts_work(cfg, [], tokens=2048)
    assert 0.9 < even["flops"] / work["flops"] < 1.1


def test_a_window_bounds_what_a_decode_step_reads():
    cfg = published()
    short = family.counts.paged_decode_work(cfg, [4096])
    long = family.counts.paged_decode_work(cfg, [8192])
    # three window layers read 4,096 positions either way, the full layer
    # all of them
    assert long["bytes"] / short["bytes"] == pytest.approx(
        (3 * 4096 + 8192) / (4 * 4096))
    assert family.counts.decode_step_flops(cfg, [4096, 100]) > 0
    assert family.counts.prefill_flops(cfg, 8192) \
        < 4.2 * family.counts.prefill_flops(cfg, 2048)


# -- the manifest: what the cell is judged on ---------------------------------------

def test_the_cell_reports_what_held_its_bound_and_its_metrics_move_that():
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    cell = PUBLISHED + ".rag-r80"
    reported = {m["name"] for m in harness.metrics_of(
        manifest, "end_to_end", cell, ())}
    # neither tail held half its bound over the driver's two sets of six
    # (PERF.md section 7 row 20), so the cell reports tokens/s and set-up
    assert reported == {"serve_tokens_per_s", "setup_s"}
    per_layer = harness.metrics_of(manifest, "per_layer", cell, reported)
    names = [m["name"] for m in per_layer]
    assert sorted(names) == sorted(
        ["moe_experts_roofline.serve", "moe_share_of_decode.serve",
         "expert_load_max_over_mean.serve",
         "window_blocks_returned_share.serve",
         "prefill_tokens_per_s.serve"])
    for m in per_layer:
        assert m["moves"] in reported, m
        spec = harness.load_json(ROOT, "benchmark", "metrics",
                                 m["name"] + ".json")
        assert (spec["name"], spec["moves"]) == (m["name"], m["moves"])
    # one definition a quantity and kind of cell: no copy of an accepted
    # metric under another suffix
    suffixes = {m["name"].rsplit(".", 1)[1] for m in manifest["per_layer"]}
    assert suffixes == {"serve", "train"}


def test_what_the_benchmark_had_is_as_it_was():
    """The accepted entries list the cells they listed: a metric that
    moves a tail cannot list a cell that reports none."""
    manifest = harness.load_json(ROOT, "BENCHMARK.json")
    cell = PUBLISHED + ".rag-r80"
    tails = {"ttft_p95_ms", "tpot_p95_ms"}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in tails or m.get("moves") in tails:
            assert cell not in m.get("workloads", [cell]), m["name"]
    new = [m["name"] for m in manifest["per_layer"]][-5:]
    assert all(manifest["per_layer"][-5 + i]["workloads"] == [cell]
               for i in range(5)), new


# -- the new readers on hand-made facts ---------------------------------------------

def test_registry_counters_read_growth_over_the_window():
    registry = {"open": {"a/0": 10.0, "a/1": 10.0, "given": 5.0,
                         "back": 1.0},
                "close": {"a/0": 40.0, "a/1": 20.0, "given": 25.0,
                          "back": 11.0}}
    facts = {"registry": registry}
    assert registry_counters.read(facts, {
        "stat": "max_over_mean", "prefix": "a/"}) == pytest.approx(1.5)
    assert registry_counters.read(facts, {
        "stat": "ratio", "over": "back", "under": "given",
        "scale": 100.0}) == pytest.approx(50.0)
    # a program from before the counters: nothing to read, not 0
    assert registry_counters.read({"registry": {"open": {}, "close": {}}},
                                  {"stat": "max_over_mean",
                                   "prefix": "a/"}) is None
    assert registry_counters.read({"registry": {"open": {}, "close": {}}},
                                  {"stat": "ratio", "over": "back",
                                   "under": "given"}) is None
    assert registry_counters.read({}, {"stat": "ratio", "over": "x",
                                       "under": "y"}) is None


def test_calls_rate_counts_tokens_not_padding():
    facts = {"traced": {"prefill": (1, 3)},
             "prefill_calls": [(0.0, 9.0, {"tokens": 999}),
                               (1.0, 1.5, {"tokens": 1000}),
                               (2.0, 2.5, {"tokens": 3000})]}
    params = {"calls": "prefill_calls", "count": "tokens"}
    assert calls_rate.read(facts, params) == pytest.approx(4000.0)
    assert calls_rate.read({"traced": None}, params) is None
    assert calls_rate.read(dict(facts, traced={"prefill": (3, 3)}),
                           params) is None

"""Family ``nemotron_h`` in the harness, end to end on the CPU at the tiny
cell's size (``cells/workloads/tiny-nemotron-h.reason.json``): a sound run
is correct, the fp8 control and every planted fault in the engine's place
are not, the family's counts follow the cut's arithmetic and the routing,
and the manifest's entries for the cell are what their files say, found by
name."""

import json
import os

import jax
import numpy as np
import pytest

import accepted
from benchmark import run as harness
from benchmark.families import nemotron_h as family
from benchmark.kinds import serve
from benchmark.tools import calibrate

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells")
ROOT = os.path.dirname(os.path.dirname(HERE))
CELL = "tiny-nemotron-h.reason"
PUBLISHED = "nemotron-3-super-120b-a12b"
NEW_METRICS = ("mamba2_scan_roofline.serve",
               "ssm_state_share_of_decode.serve",
               "latent_experts_roofline.serve",
               "latent_experts_share_of_decode.serve")
REASON = PUBLISHED + ".reason-r80"
ACCEPTED = {"configs": [PUBLISHED], "workloads": [REASON],
            "per_layer": {name: [REASON] for name in NEW_METRICS},
            "joined": {name: [REASON] for name in (
                "serve_tokens_per_s", "expert_load_max_over_mean.serve",
                "prefill_tokens_per_s.serve")}}


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
               for n in (traffic["prompt_len"]["max"], 13, 5)]
    streams = [rng.integers(1, cfg["vocab_size"], 12).tolist()
               for _ in prompts]
    got = serve.score(ctx, prompts, streams, control=fault)
    compared, _, correct = harness.decide(
        {"token_gap_mean": got["control_token_gap_mean"]},
        ctx.cell["limits"], 0)
    assert not correct, (fault, compared)


def test_the_routed_fp8_control_rounds_the_routed_products_alone():
    """``fp8_routed`` is what an fp8 expert path would be: the held
    experts' two products on the fp8 grid, and the latent projections, the
    shared expert, the mixers and the head as they were."""
    ref = family.reference
    ctx = context(12)
    cfg = ctx.config
    lo, hi = ref.seed_key(7)
    w = jax.jit(lambda lo, hi: ref.make_weights(cfg, lo, hi))(lo, hi)
    u = jax.random.normal(jax.random.PRNGKey(1), (24, cfg["hidden_size"]))
    lp = {n: v[0] for n, v in w["layers"]["moe"].items()}
    plain, low, all_low = (ref.expert_parts(cfg, lp, u, mode)
                           for mode in (False, "fp8_routed", "fp8"))
    np.testing.assert_array_equal(low[1], plain[1])       # shared expert
    assert not np.allclose(low[0], plain[0], rtol=1e-4, atol=0)
    assert not np.allclose(all_low[1], plain[1], rtol=1e-4, atol=0)
    for kind in ("mamba", "attention"):
        lp = {n: v[0] for n, v in w["layers"][kind].items()}
        np.testing.assert_array_equal(
            ref.sub_block(cfg, lp, u, kind, "fp8_routed"),
            ref.sub_block(cfg, lp, u, kind, False))
    x = jax.random.normal(jax.random.PRNGKey(2), (4, cfg["hidden_size"]))
    np.testing.assert_array_equal(ref.head(cfg, w, x, "fp8_routed"),
                                  ref.head(cfg, w, x, False))


def test_the_reference_imports_nothing_of_the_program():
    for name in ("nemotron_h_reference.py", "nemotron_h_counts.py"):
        with open(os.path.join(ROOT, "benchmark", "families", name)) as f:
            text = f.read()
        assert "apex_tpu" not in text.replace("``apex_tpu``", ""), name
    ref = open(os.path.join(ROOT, "benchmark", "families",
                            "nemotron_h_reference.py")).read()
    assert "jax.lax.scan(step" in ref and "cumsum" not in ref


# -- counts -------------------------------------------------------------------

def published():
    return harness.load_json(ROOT, "benchmark", "configs",
                             PUBLISHED + ".json")


def test_the_published_cut_is_the_issues_arithmetic():
    cfg = published()
    counts = family.counts
    # ISSUE 35: an E layer 759.2 M (128 experts of 2 x 1024 x 2688 = 704.6 M
    # + shared 44.0 M + latent 8.4 M + router 2.1 M), an M layer 27.4 M, the
    # * layer 9.4 M; 5 + 5 + 1 of them 3,942 M; embedding and head 268 M:
    # 4.21 B parameters, 8.42 GB bf16
    shapes = family.reference.weight_shapes(cfg)["layers"]
    per = {k: sum(int(np.prod(s[1:])) for s in v.values())
           for k, v in shapes.items()}
    assert abs(per["moe"] - 759.2e6) < 0.1e6
    assert abs(per["mamba"] - 27.4e6) < 0.1e6
    assert abs(per["attention"] - 9.4e6) < 0.1e6
    assert family.reference.kinds(cfg) == {"mamba": 5, "moe": 5,
                                           "attention": 1}
    assert abs(counts.n_params(cfg) - 4.21e9) < 0.01e9
    assert 8.41e9 < counts.weight_bytes(cfg) < 8.43e9
    # a slot's state: 32 x 64 x 128 float32 = 1.05 MB a layer + the tail
    assert counts.state_bytes_per_slot(cfg) == 5 * (1048576 + 3 * 2560 * 2)
    assert counts.block_bytes(cfg, 128) == 2 * 128 * 128 * 2    # 512 B a pos
    # every published width unchanged
    assert (cfg["hidden_size"], cfg["mamba_head_dim"], cfg["head_dim"],
            cfg["ssm_state_size"], cfg["conv_kernel"],
            cfg["moe_intermediate_size"], cfg["moe_latent_size"],
            cfg["moe_shared_expert_intermediate_size"], cfg["router_width"],
            cfg["num_experts_per_tok"]) == (4096, 64, 128, 128, 4, 2688,
                                            1024, 5376, 512, 22)
    assert cfg["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "mamba_num_heads": 128, "n_groups": 8, "num_attention_heads": 32,
        "num_key_value_heads": 2, "vocab_size": 131072}
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])
    assert len(cfg["hybrid_override_pattern"]) == 88
    assert cfg["hybrid_override_pattern"][:11] == "MEMEMEM*EME"


def test_the_expert_products_work_follows_the_assignments():
    cfg = published()
    per = 2.0 * 2 * 1024 * 2688           # FLOPs a row, = an expert's bytes
    # a decode step of 32 rows: ~1.4 rows an expert on ~97 of 128
    step = [[2, 1, 0, 1] * 32 + [0]] * 5
    w = family.counts.moe_experts_work(cfg, [100] * 32, expert_stats=step)
    assert w["flops"] == per * 5 * 128
    assert 5 * 96 * per <= w["bytes"] < 5 * 96 * per * 1.01
    # ISSUE 35: a prefill token costs ~1.15 GFLOP (the experts 74%), and a
    # decode step of 32 rows ~6.8 GB of weights
    even = family.counts.prefill_flops(cfg, 2048) / 2048
    assert 1.1e9 < even < 1.3e9
    assert family.counts.decode_step_flops(cfg, [300] * 32) > 32 * 1.0e9
    # with no routing kept (the run's last call) the even spread stands in
    spread = family.counts.moe_experts_work(cfg, [], tokens=2048)
    assert spread["flops"] == pytest.approx(5 * 2048 * 5.5 * per)


def test_the_scans_work_counts_the_prompts_own_tokens():
    cfg = published()
    one = family.counts.mamba2_scan_work(cfg, tokens=128)
    assert family.counts.mamba2_scan_work(cfg, tokens=129)["flops"] \
        == 2 * one["flops"]
    # 5 layers x (2 groups C B^T + 32 heads x three products of the chunk)
    assert one["flops"] == 5 * (2 * 2 * 128 ** 3 + 32 * (
        2 * 128 * 128 * 64 + 4 * 128 * 64 * 128))
    assert family.counts.mamba2_scan_work(cfg, [5, 6]) == {
        "flops": 0.0, "bytes": 0.0}          # a decode step runs no scan
    short = family.counts.paged_decode_work(cfg, [4096])
    assert short["bytes"] == 2 * 128 * 4096 * 2      # ONE KV head, 1 layer


# -- the manifest: what the cell is judged on ---------------------------------

def test_the_cell_reports_what_its_entries_say():
    manifest = accepted.load()
    reported = accepted.reported(manifest, REASON)
    assert {"serve_tokens_per_s", "setup_s"} <= reported
    assert reported <= {"serve_tokens_per_s", "setup_s", "ttft_p95_ms",
                        "tpot_p95_ms"}
    # both tails or neither
    assert ("ttft_p95_ms" in reported) == ("tpot_p95_ms" in reported)
    per_layer = harness.metrics_of(manifest, "per_layer", REASON, reported)
    names = {m["name"] for m in per_layer}
    assert set(NEW_METRICS) <= names
    assert "pool_rewrite_share.serve" not in names     # gpt2's keys
    for m in per_layer:
        assert m["moves"] in reported, m
        spec = harness.load_json(ROOT, "benchmark", "metrics",
                                 m["name"] + ".json")
        assert (spec["name"], spec["moves"]) == (m["name"], m["moves"])
    for name in NEW_METRICS:
        assert accepted.find(manifest["per_layer"], name)["unit"] == "%"
    assert accepted.find(manifest["workloads"], REASON)["chips"] == 1
    accepted.check(manifest, ACCEPTED)


def test_the_cell_is_the_issues_engine_and_traffic():
    cell = harness.load_json(ROOT, "benchmark", "workloads",
                             REASON + ".json")
    eng, mix = cell["engine"], cell["traffic_params"]
    assert (eng["max_seqs"], eng["max_len"], eng["block_size"],
            eng["prefill_buckets"], eng["speculate_k"]) \
        == (64, 4096, 128, [256, 512, 1024, 2048], 0)
    # the pool holds every slot's whole max_len, and the null block
    assert eng["num_blocks"] == {"attention": 64 * 32 + 1}
    assert mix["prompt_len"] == {"median": 256, "sigma": 0.8, "min": 32,
                                 "max": 2048}
    assert mix["output_len"] == {"median": 384, "sigma": 0.7, "min": 32,
                                 "max": 1536}
    assert mix["shuffle_block"] == 8 and cell["check"]["requests"] == 16
    assert set(serve.REQUIRED_LIMITS) <= set(cell["limits"])

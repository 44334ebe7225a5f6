"""Family ``mistral4`` in the harness, end to end on the CPU at the tiny cell's
size (``cells/workloads/tiny-mistral4.docqa.json``): a sound run is
correct, the fp8 control and the planted faults in the engine's place are
not, the published configuration is the catalog's with the cut it states,
the family's counts follow the published arithmetic, the three latent
attention metrics and the expert product's roofline read what their readers
find in a hand-made trace, the metrics a traced run can read on the CPU read
on it, and the manifest's entries for the cell are what their files say.
Entries are found by NAME, never by their place in a list: the next cell is
appended after this one."""

import importlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

import accepted
from benchmark import run as harness
from benchmark.families import mistral4 as family
from benchmark.kinds import serve
from benchmark.tools import calibrate

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells")
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = "tiny-mistral4.docqa"
PUBLISHED = "mistral-small-4-119b"
CELL = PUBLISHED + ".docqa-r80"
SOURCE = ("https://huggingface.co/mistralai/Mistral-Small-4-119B-2603/blob/"
          "main/config.json")
NEW_METRICS = ("mla_decode_roofline.serve", "mla_share_of_decode.serve",
               "mla_prefill_roofline.serve")
# accepted metrics whose lists the cell is appended to
JOINED = ("prefill_tokens_per_s.serve", "moe_experts_roofline.serve",
          "moe_share_of_decode.serve", "expert_load_max_over_mean.serve")
ACCEPTED = {"configs": [PUBLISHED], "workloads": [CELL],
            "per_layer": {name: [CELL] for name in NEW_METRICS},
            "joined": {name: [CELL]
                       for name in ("serve_tokens_per_s",) + JOINED}}
MS = 1e6          # nanoseconds


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


@pytest.mark.parametrize("fault", family.reference.FAULTS)
def test_a_planted_fault_in_the_engines_place_is_not_correct(fault):
    """What the program would serve with the fault in it (the token the
    faulty forward puts first at every scored position) fails the cell's
    limit: prompts past the 16 positions the tiny table stretches and
    answers that read the cache up to position 91, where llama 4's scale
    has stepped five times (1 + 0.1 ln 6)."""
    ctx = context(12)
    cfg = ctx.config
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg["vocab_size"], n).tolist()
               for n in (32, 24, 13)]
    streams = [rng.integers(1, cfg["vocab_size"], 60).tolist()
               for _ in prompts]
    got = serve.score(ctx, prompts, streams, control=fault)
    compared, _, correct = harness.decide(
        {"token_gap_mean": got["control_token_gap_mean"]},
        ctx.cell["limits"], 0)
    assert not correct, (fault, compared)


def test_the_reference_imports_nothing_of_the_program():
    for name in ("mistral4_reference.py", "mistral4_counts.py"):
        with open(os.path.join(ROOT, "benchmark", "families", name)) as f:
            text = f.read()
        assert "apex_tpu" not in text.replace("``apex_tpu``", ""), name
    assert set(family.reference.FAULTS) == {
        "yarn_mscale_left_out", "llama4_scale_left_out", "rotary_halves",
        "k_pe_unrotated", "kv_norm_left_out", "held_norm"}


# -- the configuration and its arithmetic -------------------------------------

def published():
    return harness.load_json(ROOT, "benchmark", "configs",
                             PUBLISHED + ".json")


CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 0, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 4096, "intermediate_size": 12288,
    "kv_lora_rank": 256, "max_position_embeddings": 1048576,
    "mlp_bias": False, "model_type": "mistral4", "moe_intermediate_size": 2048,
    "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 36,
    "num_key_value_heads": 32, "q_lora_rank": 1024, "qk_head_dim": 128,
    "qk_nope_head_dim": 64, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True,
    "rope_parameters": {"beta_fast": 32, "beta_slow": 1, "factor": 128,
                        "llama_4_scaling_beta": 0.1, "mscale": 1,
                        "mscale_all_dim": 1,
                        "original_max_position_embeddings": 8192,
                        "rope_theta": 10000, "rope_type": "yarn",
                        "type": "yarn"},
    "routed_scaling_factor": 1, "sliding_window": None,
    "tie_word_embeddings": False, "topk_group": 1, "v_head_dim": 128,
    "vocab_size": 131072}


def test_the_configuration_is_the_catalogs_with_the_cut_it_states():
    cfg = published()
    assert cfg["source"] == SOURCE and cfg["family"] == "mistral4"
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["reduced"] == reduced
    for key, value in CATALOG.items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert cfg["published"] == {k: CATALOG[k] for k in reduced}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["router_width"]) == (6, 16, 16384, 128)
    assert "eight" in cfg["deployment"] and "WHOLE" in cfg["deployment"]
    assert sorted(cfg["assumed"])[:6] == [
        "a_router", "b_softmax_scale", "c_llama4_scaling",
        "d_interleaved_pairs", "e_text_only", "f_init_and_precision"]


def test_the_chips_parameters_add_up_to_the_share_of_eight():
    """One chip of eight: attention whole, the shared expert whole, 16 of
    128 routed experts, 6 of 36 layers, 16,384 of 131,072 rows of the
    embedding and of the untied head: 2,872,635,648 parameters, 5.75 GB in
    bfloat16, 36% of the chip."""
    cfg = published()
    counts = family.counts
    layer = counts.layer_params(cfg)
    assert layer == {"attention": 28_050_688, "shared": 25_165_824,
                     "router": 532_608, "routed": 16 * 25_165_824}
    assert layer["attention"] + layer["shared"] + layer["router"] \
        == 53_749_120
    assert sum(layer.values()) == 456_402_304
    assert 6 * sum(layer.values()) == 2_738_413_824
    assert counts.n_params(cfg) == 2_872_635_648 \
        == 2_738_413_824 + 134_221_824
    assert counts.weight_bytes(cfg) == 5_745_271_296            # 5.75 GB
    assert 0.35 < counts.weight_bytes(cfg) / 16e9 < 0.37
    assert sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        family.model(cfg).param_shapes(),
        is_leaf=lambda s: isinstance(s, tuple))) == 2_872_635_648


def test_the_latent_pool_is_640_bytes_a_position_a_layer():
    """ONE row a position for all 32 heads, 256 + 64 values: 640 B a layer
    where per-head K and V would take 32 x (128 + 128) x 2 B = 16 KB, 25.6
    times more. 32 slots x 16,896 = 4,225 blocks of 128 (the null block
    included): 2.08 GB over six layers."""
    cfg = published()
    cell = harness.load_json(ROOT, "benchmark", "workloads", CELL + ".json")
    eng = cell["engine"]
    counts = family.counts
    assert counts.block_bytes(cfg, 1) == 6 * 640
    assert 32 * (128 + 128) * 2 / 640 == 25.6
    assert eng["num_blocks"] == {"mla_moe": 32 * 16896 // 128 + 1} \
        == {"mla_moe": 4225}
    pool = 4225 * counts.block_bytes(cfg, 128)
    assert pool == 2_076_672_000
    spec = family.model(cfg).cfg.cache_kinds["mla_moe"]
    assert (spec.layers, spec.latent, spec.rope) == (6, 256, 64)


def test_the_kernels_work_counts_the_live_rows_and_a_prompts_own_tokens():
    cfg = published()
    counts = family.counts
    # 2 x 32 heads x (320 + 256) FLOPs a position, 640 B a row, a layer
    one = counts.mla_decode_work(cfg, [99, 199], latent_positions=300)
    assert one["flops"] == 6 * 2 * 32 * (320 + 256) * 300
    assert one["bytes"] == 6 * (300 * 320 + 2 * 32 * (320 + 256)) * 2
    # without the program's count: one more than each slot's cached rows
    assert counts.mla_decode_work(cfg, [99, 199]) == one
    # the routed expert product: 3 x 4096 x 2048 parameters an expert, 2
    # FLOPs each a routed row; the program's count of rows a held expert
    # took, or the even spread of top-4 of 128 over the 16 held
    stats = np.zeros((6, 17), np.int64)
    stats[:, 0], stats[:, 3] = 5, 2           # two experts touched a layer
    moe = counts.moe_experts_work(cfg, [99, 199], expert_stats=stats)
    per = 3 * 4096 * 2048
    assert moe["flops"] == 2 * per * 42
    assert moe["bytes"] == 2 * per * 12 + 2 * 42 * (2 * 4096 + 2 * 2048)
    even = counts.moe_experts_work(cfg, [], tokens=4096)
    assert even["flops"] == pytest.approx(2 * per * 6 * 16 * 4096 * 4 / 128)
    pre = counts.mla_prefill_work(cfg, tokens=4096)
    assert pre["flops"] == 6 * 2 * 32 * 256 * 4096 * 4097 / 2
    assert counts.mla_prefill_work(cfg, [5, 6]) == {"flops": 0.0,
                                                    "bytes": 0.0}
    # a median request's prefill: 0.82 TFLOP of causal attention over six
    # layers (PERF.md section 6)
    assert 0.82e12 < pre["flops"] < 0.83e12
    # the projections: 56.1M FLOPs a token a layer
    assert counts.mla_projection_flops_per_token(cfg) == 2 * 28_050_688


# -- the three latent attention metrics against a hand-made trace ------------

def _ev(name, start_ms, dur_ms, text=""):
    return [name, start_ms * MS, dur_ms * MS, text]


def _trace_facts():
    """Two decode steps (0-10 ms, 20-30 ms) and one prefill (40-60 ms) on
    one chip: each decode step 4 ms in the absorbed kernel of 8 of leaf
    time; the prefill 12 ms in the flash forward."""
    ops = [
        _ev("mla_decode_attention.3", 1, 4), _ev("fusion.7", 5, 2),
        _ev("moe_experts_gate_up.2", 7, 2),
        _ev("mla_decode_attention.3", 21, 4), _ev("fusion.7", 25, 2),
        _ev("moe_experts_gate_up.2", 27, 2),
        _ev("flash_attention.5", 41, 12), _ev("fusion.9", 53, 6)]
    modules = [_ev("jit_decode_step", 0, 10), _ev("jit_decode_step", 20, 10),
               _ev("jit_prefill_step", 40, 20)]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]}
    from benchmark import trace_reduce
    decode = [(0, 1, {"contexts": [4000, 6000],
                      "latent_positions": 10002}),
              (1, 2, {"contexts": [4001, 6001],
                      "latent_positions": 10004})]
    prefill = [(2, 3, {"contexts": [], "tokens": 4096})]
    return dict(trace=trace, device_ops=trace_reduce.device_ops(trace),
                traced={"decode": (0, 2), "prefill": (0, 1)},
                decode_calls=decode, prefill_calls=prefill,
                config=published(), family=family, chips=1,
                device_kind="TPU v5 lite")


def _read(name, facts):
    import importlib
    spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(facts, spec["params"])


def test_the_three_metrics_read_what_their_readers_find():
    from benchmark import counts as chip
    facts = _trace_facts()
    peak = chip.peaks("TPU v5 lite")
    cfg = facts["config"]
    work = [family.counts.mla_decode_work(cfg, **step)
            for _, _, step in facts["decode_calls"]]
    total = {k: sum(w[k] for w in work) for k in ("flops", "bytes")}
    bound, which = chip.roofline_seconds(total, peak)
    assert which == "memory"
    assert _read("mla_decode_roofline.serve", facts) \
        == pytest.approx(100.0 * bound / 8e-3)
    # 8 of the 16 ms of leaf time inside the two decode programs
    assert _read("mla_share_of_decode.serve", facts) == pytest.approx(50.0)
    bound, which = chip.roofline_seconds(
        family.counts.mla_prefill_work(cfg, tokens=4096), peak)
    assert which == "compute"
    assert _read("mla_prefill_roofline.serve", facts) \
        == pytest.approx(100.0 * bound / 12e-3)
    # the expert product over the decode and prefill calls alike (no
    # counts in these calls: the even spread), 4 ms of the two kernels
    moe = [family.counts.moe_experts_work(cfg, **step)
           for calls in ("decode_calls", "prefill_calls")
           for _, _, step in facts[calls]]
    bound, _ = chip.roofline_seconds(
        {k: sum(w[k] for w in moe) for k in ("flops", "bytes")}, peak)
    assert _read("moe_experts_roofline.serve", facts) \
        == pytest.approx(100.0 * bound / 4e-3)


def test_a_program_without_the_kernel_reads_nothing_and_does_not_raise():
    """The parent commit: no ``mla_decode_attention`` and no flash forward
    in the trace, no ``latent_positions`` in the facts."""
    facts = _trace_facts()
    keep = lambda evs: [e for e in evs if not e[0].startswith(
        ("mla_decode", "flash"))]
    facts["device_ops"] = {c: keep(e) for c, e in facts["device_ops"].items()}
    facts["trace"]["planes"][0]["lines"][0]["events"] = keep(
        facts["trace"]["planes"][0]["lines"][0]["events"])
    for _, _, step in facts["decode_calls"]:
        step.pop("latent_positions")
    for name in NEW_METRICS:
        assert _read(name, facts) is None, name


def test_the_family_keeps_each_calls_latent_reads_with_it():
    """``step_facts`` writes into the facts of the call BEFORE this one the
    growth of the engine's count since that call began."""
    class Engine:
        last_stats = None
        latent_positions_read = 0

    class Sched:
        active = {}

    engine = Engine()
    first = family.step_facts(engine, Sched())
    engine.latent_positions_read = 37
    second = family.step_facts(engine, Sched())
    assert first["latent_positions"] == 37 and "_read_before" not in first
    engine.latent_positions_read = 40
    family.step_facts(engine, Sched())
    assert second["latent_positions"] == 3


# -- the manifest: what the cell is judged on ---------------------------------

def _metric_file(name):
    return harness.load_json(ROOT, "benchmark", "metrics", name + ".json")


def test_the_cell_reports_what_its_entries_say():
    manifest = accepted.load()
    reported = accepted.reported(manifest, CELL)
    assert {"serve_tokens_per_s", "setup_s"} <= reported
    assert not reported & {"ttft_p95_ms", "tpot_p95_ms"}
    per_layer = harness.metrics_of(manifest, "per_layer", CELL, reported)
    assert set(JOINED + NEW_METRICS) <= {m["name"] for m in per_layer}
    for m in per_layer:
        spec = _metric_file(m["name"])
        assert (spec["name"], spec["moves"], spec["layer"], spec["unit"],
                spec["better"], spec["source"]) \
            == (m["name"], m["moves"], m["layer"], m["unit"], m["better"],
                m["source"])
        assert m["moves"] in reported
    entry = accepted.find(manifest["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (PUBLISHED, "docqa-r80", 1)
    cfg_entry = accepted.find(manifest["configs"], PUBLISHED)
    assert cfg_entry["reduced"] == published()["reduced"]
    assert cfg_entry["source"] == SOURCE
    accepted.check(manifest, ACCEPTED)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_latent_metric_file_holds_the_entry_to_append(metric):
    """The three latent attention metrics are appended to the manifest,
    each listing the cell first, and the entry is what its file holds; the
    file lists no cell, and its reader reads nothing where there is
    nothing to read."""
    manifest = accepted.load()
    accepted.check(manifest, {"per_layer": {metric: [CELL]}})
    entry = accepted.find(manifest["per_layer"], metric)
    spec = _metric_file(metric)
    assert "workloads" not in spec
    assert (spec["name"], spec["unit"], spec["source"], spec["moves"]) \
        == (metric, "%", "device_trace", "serve_tokens_per_s")
    # the flash forward's layer is the one the manifest names for its file
    if metric == "mla_prefill_roofline.serve":
        assert entry["layer"] == accepted.find(
            manifest["per_layer"], "paged_decode_roofline.serve")["layer"]
    else:
        assert entry["layer"] == "kernels (apex_tpu/ops/mla.py)"
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    empty = {"traced": None, "device_ops": {}, "trace": {"planes": []},
             "chips": 1, "memory_peak_bytes": 0, "queue_wait_ms": []}
    assert reader.read(empty, spec.get("params", {})) is None


def test_the_harness_reads_the_cells_metrics_on_the_tiny_cell(
        tmp_path, capsys, monkeypatch):
    """A traced run of the tiny cell with every per-layer metric the cell
    reports: the two the program and the harness count read on the CPU;
    the five that read the chip's device trace, which a CPU run has not,
    read nothing and raise nothing (the hand-made trace above is where
    they read). The profile goes under ``tmp_path``, not the shared
    ``.bench_trace`` another process's traced run may clear."""
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    data = str(tmp_path / "data")
    shutil.copytree(CELLS, data)
    os.makedirs(os.path.join(data, "metrics"))
    for metric in JOINED + NEW_METRICS:
        shutil.copy(os.path.join(ROOT, "benchmark", "metrics",
                                 metric + ".json"),
                    os.path.join(data, "metrics"))
    manifest = str(tmp_path / "BENCHMARK.json")
    with open(manifest, "w") as f:
        json.dump({"run_seconds": 1, "end_to_end": [], "per_layer": [
            dict(m, workloads=[TINY]) for m in accepted.load()["per_layer"]
            if m["name"] in JOINED + NEW_METRICS]}, f)
    capsys.readouterr()
    harness.main(["--workload", TINY, "--data", data, "--manifest", manifest,
                  "--rehearse", "--seed", "3000000019", "--seconds", "1.0",
                  "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["readers_with_a_value"] == [
        "expert_load_max_over_mean.serve", "prefill_tokens_per_s.serve"]


def test_the_cell_is_the_engine_and_traffic_it_states():
    cell = harness.load_json(ROOT, "benchmark", "workloads", CELL + ".json")
    eng, mix = cell["engine"], cell["traffic_params"]
    assert (eng["max_seqs"], eng["max_len"], eng["block_size"],
            eng["prefill_buckets"], eng["speculate_k"], eng["cache_dtype"]) \
        == (32, 16896, 128, [1024, 2048, 4096, 8192, 12288, 16384], 0,
            "bfloat16")
    assert mix["prompt_len"] == {"median": 4096, "sigma": 0.8, "min": 512,
                                 "max": 16384}
    assert mix["output_len"] == {"median": 128, "sigma": 0.7, "min": 16,
                                 "max": 512}
    assert mix["shuffle_block"] == 8 and "burst" not in json.dumps(mix)
    # the longest prompt and the longest answer fit a slot
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        == eng["max_len"]
    assert cell["check"]["requests"] == 16
    assert set(serve.REQUIRED_LIMITS) <= set(cell["limits"])
    assert cell["limits"]["short_streams"] == cell["limits"]["unfinished"] \
        == cell["limits"]["xla_attention_programs"] == 0
    # 0.8 of the 5.0/s the program sustains (PERF.md section 4): 204
    # requests a window
    seconds = accepted.load()["run_seconds"]
    assert mix["rate_per_s"] == 0.8 * 5.0
    assert len(serve.traffic.serve_arrivals(
        mix, 16384, 4500000101, seconds)) == 204


def test_the_parent_refuses_the_model_at_its_build():
    """A program without the latent kind fails the cell at the model's
    build, before a weight is drawn (what the parent commit does with these
    files laid over it): here, a layer kind the program does not know."""
    from apex_tpu.models import pattern_decoder
    cfg = published()
    kinds = dict(pattern_decoder.LAYER_KINDS)
    try:
        del pattern_decoder.LAYER_KINDS["mla_moe"]
        with pytest.raises(ValueError, match="layer_types"):
            family.serve_engine(cfg, {}, 1)
    finally:
        pattern_decoder.LAYER_KINDS.clear()
        pattern_decoder.LAYER_KINDS.update(kinds)

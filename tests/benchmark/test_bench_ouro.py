"""Family ``ouro`` in the harness, end to end on the CPU at the tiny cell's
size (``cells/workloads/tiny-ouro.chat.json``): a sound run is correct, the
fp8 control and every planted fault in the engine's place are not, the
published configuration is the catalog's with nothing cut, the family's
counts follow the published arithmetic, the two new metrics and the four
the cell joins read what their readers find in a hand-made trace, the
metrics a traced run can read on the CPU read on it, and the manifest's
entries for the cell are what their files say. Entries are found by NAME,
never by their place in a list: the next cell is appended after this one."""

import dataclasses
import importlib
import json
import os
import shutil

import jax
import numpy as np
import pytest

import accepted
from benchmark import run as harness
from benchmark.families import ouro as family
from benchmark.kinds import serve
from benchmark.tools import calibrate

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells")
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = "tiny-ouro.chat"
PUBLISHED = "ouro-2.6b"
CELL = PUBLISHED + ".chat-r80"
SOURCE = "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/config.json"
NEW_METRICS = ("looped_decode_roofline.serve", "pool_peak_utilization.serve")
# accepted metrics whose lists the cell is appended to
JOINED = ("paged_decode_roofline.serve", "decode_step_mfu.serve",
          "prefill_own_tokens_per_s.serve", "device_idle_share.serve")
ACCEPTED = {"configs": [PUBLISHED], "workloads": [CELL],
            "per_layer": {name: [CELL] for name in NEW_METRICS},
            "joined": {name: [CELL]
                       for name in ("serve_tokens_per_s",) + JOINED}}
MS = 1e6          # nanoseconds


def context(seed):
    cell, config = harness.load_cell(CELLS, TINY)
    return harness.quiet_context(cell, config, jax.devices()[:1], seed, 1.0,
                                 rehearse=True)


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


def test_the_reference_imports_nothing_of_the_program():
    for name in ("ouro_reference.py", "ouro_counts.py"):
        with open(os.path.join(ROOT, "benchmark", "families", name)) as f:
            text = f.read()
        assert "apex_tpu" not in text.replace("``apex_tpu``", ""), name
    assert set(family.reference.FAULTS) == {
        "pass_left_out", "first_pass_cache", "pass_norm_left_out",
        "rotary_interleaved", "out_norms_left_out"}


# -- the configuration and its arithmetic -------------------------------------

def published():
    return harness.load_json(ROOT, "benchmark", "configs",
                             PUBLISHED + ".json")


CATALOG = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def test_the_configuration_is_the_catalogs_with_nothing_cut():
    cfg = published()
    assert cfg["source"] == SOURCE and cfg["family"] == "ouro"
    assert cfg["reduced"] == []
    for key, value in CATALOG.items():
        assert cfg[key] == value, key
    assert "Nothing is cut" in cfg["deployment"]
    assert sorted(cfg["assumed"])[:5] == [
        "a_sandwich_norms", "b_norm_between_passes", "c_bias_free",
        "d_rotate_half", "e_exit_gate"]
    model = family.model(cfg).cfg
    assert (model.loops, model.num_layers, model.num_attention_heads,
            model.vocab_size, model.block) == (4, 48, 16, 49152, "sandwich")


def test_the_parameters_and_the_cache_add_up_to_the_published_arithmetic():
    """51,388,416 parameters a layer, 2,667,974,657 in all (5.34 GB in
    bfloat16); K and V of 192 cached layers, 1,572,864 B a position; 16
    slots of 3 blocks of 128 and the null block: 49 blocks, 9.87 GB."""
    cfg = published()
    counts = family.counts
    assert counts.layer_params(cfg) == {
        "attention": 16_777_216, "mlp": 34_603_008, "norms": 8_192}
    assert sum(counts.layer_params(cfg).values()) == 51_388_416
    assert 48 * 51_388_416 == 2_466_643_968
    assert counts.n_params(cfg) == 2_667_974_657 \
        == 2_466_643_968 + 201_326_592 + 4_097
    assert counts.weight_bytes(cfg) == 5_335_949_314            # 5.34 GB
    assert sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        family.model(cfg).param_shapes(),
        is_leaf=lambda s: isinstance(s, tuple))) == 2_667_974_657
    assert counts.cached_layers(cfg) == 192
    assert counts.kv_bytes_per_position(cfg) == 1_572_864
    cell = harness.load_json(ROOT, "benchmark", "workloads", CELL + ".json")
    eng = cell["engine"]
    assert eng["num_blocks"] == {family.reference.KIND: 16 * 3 + 1}
    pool = 49 * counts.block_bytes(cfg, 128)
    assert pool == 9_865_003_008                                # 9.87 GB
    spec = family.model(cfg).cfg.cache_kinds
    assert spec == {family.reference.KIND: (192, None)}


def test_the_loops_work_counts_every_pass():
    """A decode step streams the layers' weights once a pass (4 x 4.93 GB)
    and the head once, reads every pass's K/V of the live slots and
    appends a row a pass; its FLOPs are the matrices 4 times a token, the
    head once, and the attention of all 192 cached layers."""
    cfg = published()
    counts = family.counts
    contexts = [100, 200]
    work = counts.looped_decode_work(cfg, contexts)
    layers = 48 * 51_388_416
    assert work["bytes"] == 2.0 * (4 * layers + 2048 * 49152 + 2048) \
        + 1_572_864 * (300 + 2) + 2.0 * 2 * 2048
    matrices = 48 * (16_777_216 + 34_603_008)
    assert work["flops"] == 2 * (2.0 * 4 * matrices + 2.0 * 49152 * 2048) \
        + 4.0 * 16 * 128 * 300 * 192
    assert work["flops"] == counts.decode_step_flops(cfg, contexts)
    paged = counts.paged_decode_work(cfg, contexts)
    assert paged == {"flops": 4.0 * 16 * 128 * 300 * 192,
                     "bytes": 300 * 1_572_864}
    # the weights alone take 24 ms a step at the v5e's 819 GB/s
    assert 0.024 < work["bytes"] / 819e9 < 0.025
    assert counts.prefill_flops(cfg, 1) == 2.0 * 4 * matrices \
        + 4.0 * 16 * 128 * 192 + 2.0 * 49152 * 2048


# -- the metrics against a hand-made trace -----------------------------------

def _ev(name, start_ms, dur_ms, text=""):
    return [name, start_ms * MS, dur_ms * MS, text]


def _trace_facts():
    """Two decode steps (0-30 ms, 40-70 ms) and one prefill (80-110 ms) on
    one chip; each decode step 6 ms in the paged kernel."""
    ops = [
        _ev("paged_decode_attention.3", 1, 6), _ev("fusion.7", 7, 20),
        _ev("paged_decode_attention.3", 41, 6), _ev("fusion.7", 47, 20),
        _ev("flash_attention.5", 81, 12), _ev("fusion.9", 93, 15)]
    modules = [_ev("jit_decode_step", 0, 30), _ev("jit_decode_step", 40, 30),
               _ev("jit_prefill_step", 80, 30)]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": modules}]}]}
    from benchmark import trace_reduce
    decode = [(0.0, 0.031, {"contexts": [100, 200]}),
              (0.040, 0.071, {"contexts": [101, 201]})]
    prefill = [(0.080, 0.111, {"contexts": [], "tokens": 96})]
    return dict(trace=trace, device_ops=trace_reduce.device_ops(trace),
                traced={"decode": (0, 2), "prefill": (0, 1)},
                decode_calls=decode, prefill_calls=prefill,
                config=published(), family=family, chips=1,
                device_kind="TPU v5 lite")


def _read(name, facts):
    spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    return reader.read(facts, spec["params"])


def test_the_device_metrics_read_what_their_readers_find():
    from benchmark import counts as chip
    facts = _trace_facts()
    peak = chip.peaks("TPU v5 lite")
    cfg = facts["config"]
    steps = [step for _, _, step in facts["decode_calls"]]
    # the whole decode program: two runs of 30 ms on the device
    work = [family.counts.looped_decode_work(cfg, **s) for s in steps]
    bound, which = chip.roofline_seconds(
        {k: sum(w[k] for w in work) for k in ("flops", "bytes")}, peak)
    assert which == "memory"
    value = _read("looped_decode_roofline.serve", facts)
    assert value == pytest.approx(100.0 * bound / 60e-3)
    assert 0 < value < 100
    # the paged kernel: 12 ms of the two steps
    work = [family.counts.paged_decode_work(cfg, **s) for s in steps]
    bound, _ = chip.roofline_seconds(
        {k: sum(w[k] for w in work) for k in ("flops", "bytes")}, peak)
    assert _read("paged_decode_roofline.serve", facts) \
        == pytest.approx(100.0 * bound / 12e-3)
    # the decode steps' FLOPs over their 62 ms of wall time
    flops = sum(family.counts.decode_step_flops(cfg, **s) for s in steps)
    assert _read("decode_step_mfu.serve", facts) == pytest.approx(
        100.0 * flops / (62e-3 * peak["bf16_flops_per_s"]))
    # 107 ms from the first op to the last, 79 of them busy
    assert _read("device_idle_share.serve", facts) \
        == pytest.approx(100.0 * (1 - 79 / 107))


def test_the_program_roofline_counts_the_runs_the_device_shows():
    """One of two traced calls never reached the device (the profiler
    stalled its dispatch): the work is the mean call's, once."""
    facts = _trace_facts()
    full = _read("looped_decode_roofline.serve", facts)
    lines = facts["trace"]["planes"][0]["lines"]
    lines[1]["events"] = lines[1]["events"][1:]
    from benchmark import trace_reduce
    facts["device_ops"] = trace_reduce.device_ops(facts["trace"])
    assert _read("looped_decode_roofline.serve", facts) \
        == pytest.approx(full, rel=1e-4)


def test_a_program_without_the_looped_stack_reads_nothing_and_does_not_raise():
    """The parent commit: no decode program in the trace, no high-water
    gauge in the registry."""
    facts = _trace_facts()
    lines = facts["trace"]["planes"][0]["lines"]
    lines[1]["events"] = [e for e in lines[1]["events"]
                          if not e[0].startswith("jit_decode")]
    facts["registry"] = {"open": {"serve/pool_utilization": 0.1},
                         "close": {"serve/pool_utilization": 0.4}}
    for name in NEW_METRICS:
        assert _read(name, facts) is None, name
    facts["registry"]["close"]["serve/pool_utilization_max"] = 0.55
    assert _read("pool_peak_utilization.serve", facts) \
        == pytest.approx(55.0)


# -- the manifest: what the cell is judged on ---------------------------------

def _metric_file(name):
    return harness.load_json(ROOT, "benchmark", "metrics", name + ".json")


def test_the_cell_reports_what_its_entries_say():
    manifest = accepted.load()
    reported = accepted.reported(manifest, CELL)
    assert {"serve_tokens_per_s", "setup_s"} <= reported
    assert not reported & {"ttft_p95_ms", "tpot_p95_ms"}
    per_layer = harness.metrics_of(manifest, "per_layer", CELL, reported)
    assert {m["name"] for m in per_layer} == set(JOINED + NEW_METRICS)
    for m in per_layer:
        spec = _metric_file(m["name"])
        assert (spec["name"], spec["moves"], spec["layer"], spec["unit"],
                spec["better"], spec["source"]) \
            == (m["name"], m["moves"], m["layer"], m["unit"], m["better"],
                m["source"])
        assert m["moves"] in reported
    entry = accepted.find(manifest["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (PUBLISHED, "chat-r80", 1)
    cfg_entry = accepted.find(manifest["configs"], PUBLISHED)
    assert cfg_entry["reduced"] == [] and cfg_entry["source"] == SOURCE
    accepted.check(manifest, ACCEPTED)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_new_metric_file_holds_the_entry_to_append(metric):
    manifest = accepted.load()
    accepted.check(manifest, {"per_layer": {metric: [CELL]}})
    spec = _metric_file(metric)
    assert "workloads" not in spec
    assert (spec["name"], spec["unit"], spec["moves"]) \
        == (metric, "%", "serve_tokens_per_s")
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    empty = {"traced": None, "device_ops": {}, "trace": {"planes": []},
             "chips": 1, "memory_peak_bytes": 0, "queue_wait_ms": []}
    assert reader.read(empty, spec.get("params", {})) is None


def test_the_harness_reads_the_cells_metrics_on_the_tiny_cell(
        tmp_path, capsys, monkeypatch):
    """A traced run of the tiny cell with every per-layer metric the cell
    reports but the decode steps' share of the peak, which asks the chip's
    table for the CPU and so reads on the chip alone (the hand-made trace
    above is where it reads): the two that the program and the harness
    count read on the CPU; the three that read the chip's device trace,
    which a CPU run has not, read nothing and raise nothing. The profile
    goes under ``tmp_path``, not the shared ``.bench_trace`` another
    process's traced run may clear."""
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    data = str(tmp_path / "data")
    shutil.copytree(CELLS, data)
    os.makedirs(os.path.join(data, "metrics"))
    names = tuple(n for n in JOINED + NEW_METRICS
                  if n != "decode_step_mfu.serve")
    for metric in names:
        shutil.copy(os.path.join(ROOT, "benchmark", "metrics",
                                 metric + ".json"),
                    os.path.join(data, "metrics"))
    manifest = str(tmp_path / "BENCHMARK.json")
    with open(manifest, "w") as f:
        json.dump({"run_seconds": 1, "end_to_end": [], "per_layer": [
            dict(m, workloads=[TINY]) for m in accepted.load()["per_layer"]
            if m["name"] in names]}, f)
    capsys.readouterr()
    harness.main(["--workload", TINY, "--data", data, "--manifest", manifest,
                  "--rehearse", "--seed", "3000000019", "--seconds", "1.0",
                  "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["readers_with_a_value"] == [
        "pool_peak_utilization.serve", "prefill_own_tokens_per_s.serve"]


def test_the_cell_is_the_engine_and_traffic_it_states():
    cell = harness.load_json(ROOT, "benchmark", "workloads", CELL + ".json")
    eng, mix = cell["engine"], cell["traffic_params"]
    assert (eng["max_seqs"], eng["max_len"], eng["block_size"],
            eng["prefill_buckets"], eng["speculate_k"], eng["cache_dtype"]) \
        == (16, 384, 128, [128, 256], 0, "bfloat16")
    assert mix["prompt_len"] == {"median": 96, "sigma": 0.8, "min": 16,
                                 "max": 256}
    assert mix["output_len"] == {"median": 64, "sigma": 0.7, "min": 8,
                                 "max": 128}
    assert mix["shuffle_block"] == 8 and "burst" not in json.dumps(mix)
    # the longest prompt and the longest answer fit a slot, whose whole
    # max_len the pool holds
    assert mix["prompt_len"]["max"] + mix["output_len"]["max"] \
        == eng["max_len"] == 3 * eng["block_size"]
    assert cell["check"]["requests"] == 16
    assert set(serve.REQUIRED_LIMITS) <= set(cell["limits"])
    assert cell["limits"]["short_streams"] == cell["limits"]["unfinished"] \
        == cell["limits"]["xla_attention_programs"] == 0
    # 0.8 of the 3.5/s the program sustains (PERF.md section 4): 143
    # requests a window
    seconds = accepted.load()["run_seconds"]
    assert mix["rate_per_s"] == pytest.approx(0.8 * 3.5)
    assert len(serve.traffic.serve_arrivals(
        mix, 49152, 4500003101, seconds)) == 143


def test_the_parent_refuses_the_model_at_its_build(monkeypatch):
    """A program without the looped stack fails the cell at the model's
    build, before a weight is drawn: here, a configuration class without
    ``loops`` (the parent commit's), which the family's build hands it."""
    from apex_tpu.models import pattern_decoder
    keep = [f for f in dataclasses.fields(pattern_decoder.PatternDecoderConfig)
            if f.name != "loops"]
    parent = dataclasses.make_dataclass(
        "PatternDecoderConfig",
        [(f.name, f.type, dataclasses.field(default=f.default))
         if f.default is not dataclasses.MISSING else (f.name, f.type)
         for f in keep], frozen=True)
    monkeypatch.setattr(pattern_decoder, "PatternDecoderConfig", parent)
    drawn = []
    monkeypatch.setattr(family, "make_weights",
                        lambda *a: drawn.append(a))
    with pytest.raises(TypeError, match="loops"):
        family.serve_engine(published(), {}, 1)
    assert drawn == []

"""BENCHMARK.json against the files it names: every lookup is by name
(``accepted.py``), and the per-cell rules hold with a probe cell and its own
entries appended as the next PR would append them."""

import importlib

import pytest

import accepted
from benchmark import run as harness

ROOT = accepted.ROOT
BENCH = accepted.BENCH
MANIFEST = accepted.load()
CELLS = [w["name"] for w in MANIFEST["workloads"]]
CHAT, TRAIN = "gpt2-large.chat-r80", "gpt2-medium.pretrain-16k"
# the two gpt2 cells as accepted, the end-to-end metrics and the overlap
# counter; the two tails are no longer end to end (PERF.md section 2)
ACCEPTED = {
    "configs": ["gpt2-medium", "gpt2-large"],
    "workloads": [TRAIN, CHAT],
    "end_to_end": {
        "train_tokens_per_s": {"unit": "tokens/s", "better": "higher",
                               "bound": 0.01, "source": "host_clock",
                               "workloads": [TRAIN]},
        "serve_tokens_per_s": {"unit": "tokens/s", "better": "higher",
                               "bound": 0.025, "source": "host_clock",
                               "workloads": [CHAT]},
        "setup_s": {"unit": "s", "better": "lower", "bound": 0.1,
                    "source": "host_clock"},
    },
    "per_layer": {
        "decode_roundtrip_ms.serve": [CHAT],
        "decode_step_mfu.serve": [CHAT],
        "device_idle_share.serve": [CHAT],
        "device_idle_share.train": [TRAIN],
        "flash_attention_roofline.train": [TRAIN],
        "paged_decode_roofline.serve": [CHAT],
        "peak_hbm_gb.train": [TRAIN],
        "queue_wait_p95_ms.serve": [CHAT],
        "step_mfu.train": [TRAIN],
        "decode_overlap_share.serve": [CHAT],
    },
}


def cell_file(name):
    return harness.load_json(BENCH, "workloads", name + ".json")


def cell_agrees_with_its_files(manifest, cell):
    entry = accepted.find(manifest["workloads"], cell)
    data = cell_file(cell)
    assert data["config"] == entry["config"]
    assert data["chips"] == entry["chips"]
    assert cell == f'{entry["config"]}.{entry["traffic"]}'
    cfg = accepted.find(manifest["configs"], data["config"])
    assert cfg["file"] == f'benchmark/configs/{data["config"]}.json'
    on_disk = harness.load_json(ROOT, cfg["file"])
    assert on_disk["source"] == cfg["source"]
    assert on_disk["reduced"] == cfg["reduced"]
    kind = importlib.import_module("benchmark.kinds." + data["kind"])
    assert harness.load_family(on_disk).__name__ \
        == "benchmark.families." + on_disk["family"]
    # every number `correct` has to compare has a limit, set from chip
    # readings (PERF.md); a number without one is printed, not compared.
    # Which numbers those are is the kind's own to say (REQUIRED_LIMITS), so
    # a cell of a kind a later PR brings is held to that kind's list
    assert kind.REQUIRED_LIMITS
    for name in kind.REQUIRED_LIMITS:
        assert data["limits"].get(name) is not None, (cell, name)


def cell_reports_setup_another_end_to_end_and_a_per_layer(manifest, cell):
    e2e = accepted.reported(manifest, cell)
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.metrics_of(manifest, "per_layer", cell, e2e)
    assert layer
    for m in layer:
        # a cell that reports a per-layer metric reports what it moves
        assert m["moves"] in e2e, (cell, m["name"])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_agree_with_the_manifest(cell):
    cell_agrees_with_its_files(MANIFEST, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_per_layer(cell):
    cell_reports_setup_another_end_to_end_and_a_per_layer(MANIFEST, cell)


def test_the_accepted_entries_are_as_their_prs_set_them():
    accepted.check(MANIFEST, ACCEPTED)


def test_a_probe_cell_with_its_own_entries_turns_no_check_red():
    """The manifest as the next ``model_config`` PR leaves it: a probe
    configuration, a one-chip cell and three per-layer entries appended,
    the cell appended to ``serve_tokens_per_s`` and to every per-layer
    entry that moves it. Every module's ``ACCEPTED`` and the per-cell rules
    of the cells that are here hold on it as they hold on the manifest."""
    probe = accepted.with_probe(MANIFEST)
    assert accepted.per_layer_of(probe, accepted.PROBE_CELL) \
        >= set(accepted.PROBE_METRICS)
    found = accepted.modules()
    # every module that holds manifest entries states them as data
    for name in ("test_bench_manifest", "test_bench_span_readers",
                 "test_bench_registry_untraced", "test_bench_cohere2_moe",
                 "test_bench_nemotron_h", "test_bench_jamba",
                 "test_bench_mistral4"):
        assert name in found, name
    for name, expected in found.items():
        accepted.check(MANIFEST, expected)
        accepted.check(probe, expected)
    for cell in CELLS:
        cell_agrees_with_its_files(probe, cell)
        cell_reports_setup_another_end_to_end_and_a_per_layer(probe, cell)
        # the cell reports what it reported, and nothing of the probe's
        assert accepted.per_layer_of(probe, cell) \
            == accepted.per_layer_of(MANIFEST, cell)
    bounds_and_sources_fit_the_contract(probe)


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_metric_file_matches_manifest_and_names_a_reader(metric):
    entry = accepted.find(MANIFEST["per_layer"], metric)
    spec = harness.load_json(BENCH, "metrics", metric + ".json")
    for key in accepted.FILE_KEYS:
        assert spec[key] == entry[key], (metric, key)
    # which cells report a metric is the manifest's alone to say: a later
    # PR lists its new cell there and edits no file that is here
    assert "workloads" not in spec
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert callable(reader.read)
    if "share" in metric or "roofline" in metric or "mfu" in metric:
        assert entry["unit"] == "%"


def test_a_reader_with_nothing_to_read_returns_nothing():
    facts = {"traced": None, "device_ops": {}, "trace": {"planes": []},
             "chips": 1, "memory_peak_bytes": 0, "queue_wait_ms": []}
    for metric in MANIFEST["per_layer"]:
        spec = harness.load_json(BENCH, "metrics", metric["name"] + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        assert reader.read(facts, spec.get("params", {})) is None


def bounds_and_sources_fit_the_contract(manifest):
    names = [m["name"] for m in manifest["end_to_end"]]
    assert "setup_s" in names and len(set(names)) == len(names)
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)
    assert all(len(w["why"]) <= 200 for w in manifest["workloads"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [m["name"] for m in manifest[group]]
        assert len(set(names)) == len(names), group


def test_bounds_and_sources_fit_the_contract():
    bounds_and_sources_fit_the_contract(MANIFEST)

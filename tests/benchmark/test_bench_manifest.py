"""BENCHMARK.json against the files it names: every lookup is by name."""

import importlib
import json
import os

import pytest

from benchmark import run as harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def cell_file(name):
    return harness.load_json(BENCH, "workloads", name + ".json")


def reported_end_to_end(cell):
    return {m["name"] for m in harness.metrics_of(MANIFEST, "end_to_end",
                                                  cell, ())}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_agree_with_the_manifest(cell):
    entry = next(w for w in MANIFEST["workloads"] if w["name"] == cell)
    data = cell_file(cell)
    assert data["config"] == entry["config"]
    assert data["chips"] == entry["chips"]
    assert cell == f'{entry["config"]}.{entry["traffic"]}'
    cfg = next(c for c in MANIFEST["configs"] if c["name"] == data["config"])
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


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_per_layer(cell):
    e2e = reported_end_to_end(cell)
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = harness.metrics_of(MANIFEST, "per_layer", cell, e2e)
    assert layer
    for m in layer:
        # a cell that reports a per-layer metric reports what it moves
        assert m["moves"] in e2e, (cell, m["name"])


@pytest.mark.parametrize("metric", [m["name"] for m in MANIFEST["per_layer"]])
def test_metric_file_matches_manifest_and_names_a_reader(metric):
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == metric)
    spec = harness.load_json(BENCH, "metrics", metric + ".json")
    for key in ("unit", "better", "source", "layer", "moves"):
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


def test_bounds_and_sources_fit_the_contract():
    names = [m["name"] for m in MANIFEST["end_to_end"]]
    assert "setup_s" in names and len(set(names)) == len(names)
    for m in MANIFEST["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)
    assert all(len(w["why"]) <= 200 for w in MANIFEST["workloads"])

"""The one rule by which the tests under ``tests/benchmark/`` hold the
manifest's accepted entries, and the probe that guards it.

An accepted entry (a configuration, a cell, an end-to-end or a per-layer
metric) is found by its ``name``. Its fields equal what its PR set, and its
``workloads`` list BEGINS with the cells its PR listed, in that order, and
may go on. A later PR appends its own entries at the end of each list and
its cell to the ``workloads`` of the entries it joins, and turns no test
red. So nothing here looks at an entry's place in a list or at the last
element of one, and no list of cells is compared for equality.

A test module states what its PR set as data, ``ACCEPTED``, and applies it
with ``check(manifest, ACCEPTED)``::

    ACCEPTED = {
        "configs": [name, ...],
        "workloads": [name, ...],
        "end_to_end": {name: {field: value, "workloads": [cell, ...]}},
        "per_layer": {name: [cell, ...]},
        "joined": {metric: [cell, ...]},
    }

Every key is optional. A configuration, a cell and a per-layer entry are
held to their files (``benchmark/configs``, ``workloads``, ``metrics``), an
end-to-end entry, which has none, to the fields given; ``joined`` names the
cells the PR appended to entries of earlier PRs, each listed once.

``test_bench_manifest.py`` applies every module's ``ACCEPTED`` once more to
the manifest with a probe configuration, cell and three entries appended
(``with_probe``): what holds there holds for the next PR that appends the
same way."""

import copy
import glob
import importlib
import os

from benchmark import run as harness

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
FILE_KEYS = ("unit", "better", "source", "layer", "moves")

PROBE_CONFIG = "probe"
PROBE_CELL = "probe.chat-r80"
PROBE_METRICS = ("probe_kernel_roofline.serve", "probe_share_of_decode.serve",
                 "probe_state_share.serve")
PROBE_MOVES = "serve_tokens_per_s"


def load():
    """The repository's manifest, a fresh copy each call."""
    return harness.load_json(ROOT, "BENCHMARK.json")


def find(entries, name):
    """The one entry of ``entries`` called ``name``."""
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, (name, len(found))
    return found[0]


def find_metric(manifest, name):
    return find(manifest["end_to_end"] + manifest["per_layer"], name)


def begins_with(listed, cells, what):
    assert listed[:len(cells)] == list(cells), (what, listed, cells)


def reported(manifest, cell):
    """The names of the end-to-end metrics ``cell`` reports."""
    return {m["name"] for m in harness.metrics_of(manifest, "end_to_end",
                                                  cell, ())}


def per_layer_of(manifest, cell):
    """The names of the per-layer metrics ``cell`` reports."""
    return {m["name"] for m in harness.metrics_of(
        manifest, "per_layer", cell, reported(manifest, cell))}


def check(manifest, accepted):
    """Holds ``manifest`` to one module's ``ACCEPTED``."""
    for name in accepted.get("configs", ()):
        entry = find(manifest["configs"], name)
        assert entry["file"] == f"benchmark/configs/{name}.json", name
        on_disk = harness.load_json(ROOT, entry["file"])
        assert (entry["source"], entry["reduced"]) \
            == (on_disk["source"], on_disk["reduced"]), name
        assert len(entry["why"]) <= 200, name
    for name in accepted.get("workloads", ()):
        entry = find(manifest["workloads"], name)
        data = harness.load_json(BENCH, "workloads", name + ".json")
        assert (entry["config"], entry["chips"]) \
            == (data["config"], data["chips"]), name
        assert name == f'{entry["config"]}.{entry["traffic"]}', name
        assert len(entry["why"]) <= 200, name
    for name, want in accepted.get("end_to_end", {}).items():
        entry = find(manifest["end_to_end"], name)
        for key, value in want.items():
            if key != "workloads":
                assert entry[key] == value, (name, key)
        if "workloads" in want:
            begins_with(entry["workloads"], want["workloads"], name)
        else:
            assert "workloads" not in entry, name
    for name, cells in accepted.get("per_layer", {}).items():
        entry = find(manifest["per_layer"], name)
        data = harness.load_json(BENCH, "metrics", name + ".json")
        assert data["name"] == name
        for key in FILE_KEYS:
            assert entry[key] == data[key], (name, key)
        begins_with(entry["workloads"], cells, name)
    for name, cells in accepted.get("joined", {}).items():
        listed = find_metric(manifest, name)["workloads"]
        for cell in cells:
            assert listed.count(cell) == 1, (name, cell)


def modules():
    """Every test module here that states ``ACCEPTED``, by name."""
    found = {}
    for path in sorted(glob.glob(os.path.join(HERE, "test_bench_*.py"))):
        name = os.path.splitext(os.path.basename(path))[0]
        module = importlib.import_module(name)
        if hasattr(module, "ACCEPTED"):
            found[name] = module.ACCEPTED
    return found


def with_probe(manifest):
    """``manifest`` as the next PR that brings a serve cell would leave it:
    a configuration, a one-chip cell and three per-layer entries appended,
    and the cell appended to ``serve_tokens_per_s`` and to every per-layer
    entry that moves it."""
    m = copy.deepcopy(manifest)
    m["configs"].append({
        "name": PROBE_CONFIG, "source": "https://huggingface.co/probe",
        "file": f"benchmark/configs/{PROBE_CONFIG}.json", "reduced": [],
        "why": "a configuration appended after the accepted ones"})
    m["workloads"].append({
        "name": PROBE_CELL, "config": PROBE_CONFIG, "traffic": "chat-r80",
        "chips": 1, "why": "a cell appended after the accepted ones"})
    find(m["end_to_end"], PROBE_MOVES)["workloads"].append(PROBE_CELL)
    for entry in m["per_layer"]:
        if entry["moves"] == PROBE_MOVES and "workloads" in entry:
            entry["workloads"].append(PROBE_CELL)
    m["per_layer"] += [
        {"name": name, "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "probe layer",
         "moves": PROBE_MOVES, "workloads": [PROBE_CELL]}
        for name in PROBE_METRICS]
    return m

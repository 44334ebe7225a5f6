"""The reader ``registry_untraced`` (the program's own counters and span
histograms over the untraced part of a traced run, ``open`` to
``trace_from``) on hand-made snapshots, the four metrics that read it
against their manifest entries, and the harness reading them on the CPU
at the tiny serve cell's size. Entries are found by NAME."""

import json
import os
import shutil

import pytest

import accepted
from benchmark import run as harness
from benchmark.readers import registry_untraced
from benchmark.tools import serve_phases

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells")
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = accepted.load()
CHAT, RAG = "gpt2-large.chat-r80", "command-a-plus-05-2026.rag-r80"
REASON, JAMBA = "nemotron-3-super-120b-a12b.reason-r80", "jamba2-3b.chat-r80"
NEW = {  # metric -> the cells it was accepted with, first in its list
    "prefill_own_tokens_per_s.serve": [RAG, REASON, JAMBA],
    "prefill_drain_ms.serve": [CHAT],
    "decode_wait_ms.serve": [CHAT],
    "admit_share_of_loop.serve": [CHAT, RAG, REASON, JAMBA],
}
ACCEPTED = {"per_layer": NEW}
P = "serve/span_ms/"


def spec(metric):
    return harness.load_json(BENCH, "metrics", metric + ".json")


def snapshot(prefill_tokens, prefill_ms, drain_ms, prefills, wait_ms, waits,
             admit_ms, step_ms):
    return {"serve/prefill_tokens": prefill_tokens,
            P + "engine.prefill_sum": prefill_ms,
            P + "engine.prefill_count": prefills,
            P + "prefill.drain_sum": drain_ms,
            P + "prefill.drain_count": prefills / 2,
            P + "decode.wait_sum": wait_ms, P + "decode.wait_count": waits,
            P + "sched.admit_sum": admit_ms, P + "sched.step_sum": step_ms,
            "serve/decode_steps": waits}


OPEN = snapshot(100, 50.0, 10.0, 4, 20.0, 10, 60.0, 200.0)
# from open: 20,000 tokens in 1,000 - 200 ms of the prefills' own time; 2 ms
# of drain a prefill; 1.5 ms a wait; admissions 300 of 1,200 ms of steps
TRACE_FROM = snapshot(20100, 1050.0, 210.0, 104, 1520.0, 1010, 360.0,
                      1400.0)
# the profiler's stall past trace_from: must not be read
CLOSE = snapshot(40100, 9050.0, 8210.0, 204, 9520.0, 2010, 9360.0, 20000.0)
EXPECTED = {"prefill_own_tokens_per_s.serve": 25000.0,
            "prefill_drain_ms.serve": 2.0,
            "decode_wait_ms.serve": 1.5,
            "admit_share_of_loop.serve": 25.0}


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_reader_reads_growth_from_open_to_trace_from(metric):
    facts = {"registry": {"open": OPEN, "trace_from": TRACE_FROM,
                          "trace_to": CLOSE, "close": CLOSE}}
    value = registry_untraced.read(facts, spec(metric)["params"])
    assert value == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_reader_gives_none_never_0_for_nothing_to_read(metric):
    params = spec(metric)["params"]
    # the parent program: its registry has the scheduler's counters and no
    # serve/span_ms/* histogram
    parent = {k: v for k, v in OPEN.items() if not k.startswith(P)}
    parent_later = {k: v for k, v in TRACE_FROM.items()
                    if not k.startswith(P)}
    for registry in (
            {"open": parent, "trace_from": parent_later, "close": CLOSE},
            {"open": OPEN, "close": CLOSE},               # untraced run
            {"open": OPEN, "trace_from": OPEN, "close": CLOSE},  # no growth
            {}):
        assert registry_untraced.read({"registry": registry}, params) is None
    assert registry_untraced.read({}, params) is None


@pytest.mark.parametrize("metric", sorted(NEW))
def test_the_entries_match_their_files(metric):
    accepted.check(MANIFEST, {"per_layer": {metric: NEW[metric]}})
    entry = accepted.find(MANIFEST["per_layer"], metric)
    assert spec(metric)["reader"] == "registry_untraced"
    assert entry["source"] == "program_counter"
    # a layer the manifest already had, letter for letter
    assert entry["layer"] in {m["layer"] for m in MANIFEST["per_layer"]
                              if m["name"] not in NEW}
    # each listed cell reports what the metric moves
    for cell in entry["workloads"]:
        assert entry["moves"] in accepted.reported(MANIFEST, cell), cell


def test_the_entries_were_appended():
    """Found by name, each once, its cells first in its list: entries
    appended after these four, and cells appended to their lists, leave
    this as it is."""
    accepted.check(MANIFEST, ACCEPTED)


def test_the_harness_reads_all_four_on_the_tiny_cell(tmp_path, capsys):
    """Through ``tools/serve_phases.py``, which runs the harness's own
    main: the result line names the four readers, and the tool reads the
    loop's phases and the stall count off the same registry."""
    data = str(tmp_path / "data")
    shutil.copytree(CELLS, data)
    os.makedirs(os.path.join(data, "metrics"))
    for metric in NEW:
        shutil.copy(os.path.join(BENCH, "metrics", metric + ".json"),
                    os.path.join(data, "metrics"))
    manifest = str(tmp_path / "BENCHMARK.json")
    with open(manifest, "w") as f:
        json.dump({"run_seconds": 1, "end_to_end": [], "per_layer": [
            dict(m, workloads=["tiny.chat"]) for m in MANIFEST["per_layer"]
            if m["name"] in NEW]}, f)
    out = str(tmp_path / "phases.json")
    capsys.readouterr()
    serve_phases.main(["--workload", "tiny.chat", "--data", data,
                       "--manifest", manifest, "--rehearse", "--seed",
                       "3000000019", "--seconds", "1.0", "--trace", "1",
                       "--out", out])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["readers_with_a_value"] == sorted(NEW)
    with open(out) as f:
        got = json.load(f)
    window, untraced = got["phases"]["window"], got["phases"]["untraced"]
    assert set(window) == set(serve_phases.TIMED)
    for name in serve_phases.TIMED:
        assert 0 < untraced[name]["count"] <= window[name]["count"]
    assert window["engine.prefill"]["count"] == line["attempted"]
    assert got["stalls"]["bound_ms"] >= serve_phases.STALL_MS
    assert got["bench_calls"]["decode"] > 0
    assert got["traced"]["prefill_waits"] > 0


def test_the_tool_reads_nothing_of_a_program_without_the_histograms():
    parent = {k: v for k, v in CLOSE.items() if not k.startswith(P)}
    assert serve_phases.phases({}, parent) is None
    assert serve_phases.stalls({}, parent) is None


def test_the_stall_count_is_read_off_the_buckets():
    bounds = (10.0, 55.0, 111.342, 140.5)
    snap = lambda counts: dict(
        {f"{P}decode.wait_count": float(sum(counts))},
        **{f"{P}decode.wait_bucket_le_{b:g}": float(sum(counts[:i + 1]))
           for i, b in enumerate(bounds)},
        **{f"{P}decode.wait_bucket_le_inf": float(sum(counts))})
    lo, hi = snap([5, 0, 0, 0, 0]), snap([900, 40, 7, 2, 1])
    # past 111.342: the 2 up to 140.5 and the 1 past it
    assert serve_phases.stalls(lo, hi) == {"bound_ms": 111.342, "count": 3.0}

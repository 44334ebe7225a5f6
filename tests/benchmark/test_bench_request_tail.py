"""The request tails of a serve cell as per-layer metrics: the reader on
hand-made latencies, the chat cell's manifest entries, and the harness
reading both on the tiny cell."""

import json
import os
import shutil

import pytest

import accepted
from benchmark import run as harness
from benchmark.readers import request_tail

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = os.path.join(HERE, "cells")
MANIFEST = accepted.load()
CHAT = "gpt2-large.chat-r80"
TAILS = ["ttft_p95_ms.serve", "tpot_p95_ms.serve"]
ACCEPTED = {"per_layer": {name: [CHAT] for name in TAILS}}
TRACED = {"t_from": 10.0, "t_to": 13.0}


def rows(n, done_from=0.0):
    """``n`` finished requests, the i-th done at ``done_from + i / 10`` s,
    with a first token i ms after it was due and i / 10 ms a token."""
    return [(done_from + i / 10.0, float(i), i / 10.0) for i in range(n)]


def test_the_tail_counts_only_what_finished_before_the_trace():
    facts = {"traced": TRACED,
             "latencies_ms": rows(100) + rows(50, done_from=10.0)}
    # the first 100 finished by 9.9 s; those done at 10 s and after are out
    assert request_tail.read(facts, {"latency": "ttft", "q": 95}) == 94.0
    assert request_tail.read(facts, {"latency": "tpot", "q": 95}) \
        == pytest.approx(9.4)
    assert request_tail.read(facts, {"latency": "ttft", "q": 50}) == 49.0


def test_a_request_of_one_token_has_no_tpot_and_still_a_ttft():
    facts = {"traced": TRACED,
             "latencies_ms": [(1.0, 5.0, None), (2.0, 7.0, 0.5)]}
    assert request_tail.read(facts, {"latency": "tpot"}) == 0.5
    assert request_tail.read(facts, {"latency": "ttft"}) == 7.0
    facts["latencies_ms"] = [(1.0, 5.0, None)]
    assert request_tail.read(facts, {"latency": "tpot"}) is None


@pytest.mark.parametrize("facts", [
    {"traced": None, "latencies_ms": rows(10)},
    {"traced": TRACED, "latencies_ms": []},
    {"traced": TRACED, "latencies_ms": rows(10, done_from=10.0)},
])
def test_nothing_to_read_is_none(facts):
    assert request_tail.read(facts, {"latency": "ttft"}) is None


def test_the_entries_were_appended_and_match_their_files():
    accepted.check(MANIFEST, ACCEPTED)


def test_the_chat_cell_reports_its_tails_per_layer_and_not_end_to_end():
    """Neither tail holds a bound in the chat cell (PERF.md section 2), so
    it reports tokens/s and set-up end to end, and the tails, and every
    per-layer metric it reports, move tokens/s."""
    reported = accepted.reported(MANIFEST, CHAT)
    assert reported == {"serve_tokens_per_s", "setup_s"}
    names = {m["name"] for m in MANIFEST["end_to_end"]}
    assert not names & {"ttft_p95_ms", "tpot_p95_ms"}
    layer = harness.metrics_of(MANIFEST, "per_layer", CHAT, reported)
    assert set(TAILS) <= {m["name"] for m in layer}
    for m in layer:
        assert m["moves"] == "serve_tokens_per_s", m["name"]


def test_the_harness_reads_both_on_the_tiny_cell(tmp_path, capsys):
    data = str(tmp_path / "data")
    shutil.copytree(CELLS, data)
    os.makedirs(os.path.join(data, "metrics"))
    for metric in TAILS:
        shutil.copy(os.path.join(accepted.BENCH, "metrics", metric + ".json"),
                    os.path.join(data, "metrics"))
    manifest = str(tmp_path / "BENCHMARK.json")
    with open(manifest, "w") as f:
        json.dump({"run_seconds": 3, "end_to_end": [], "per_layer": [
            dict(accepted.find(MANIFEST["per_layer"], name),
                 workloads=["tiny.chat"]) for name in TAILS]}, f)
    capsys.readouterr()
    harness.main(["--workload", "tiny.chat", "--data", data, "--manifest",
                  manifest, "--rehearse", "--seed", "3000000023",
                  "--seconds", "3.0", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["readers_with_a_value"] == sorted(TAILS)

"""The family seam: all the harness knows of an architecture sits in
``benchmark/families/<family>.py``, found by the name the configuration
gives. A second family registered at run time, with a cell, a metric and a
reader of its own, runs through ``run.py`` with no edit to a file that is
there; the kinds, the readers, the tools and ``run.py`` name nothing of
GPT-2; the tools still start on a tiny cell through the family."""

import glob
import importlib
import json
import os
import re
import sys
import types

import pytest

from benchmark import run as harness
from benchmark.tools import calibrate, poisson_tails, sweep_rate

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CELLS = os.path.join(HERE, "cells")
BENCH = os.path.join(ROOT, "benchmark")


# -- a second family, brought as new files and modules only -------------------

def toy_family(asked):
    """The gpt2 family with a vocabulary slice, an engine key of its own
    and one more entry in ``step_facts``; ``asked`` records what the
    harness came for."""
    gpt2 = importlib.import_module("benchmark.families.gpt2")
    toy = types.ModuleType("benchmark.families.toy")

    def vocab(cfg):
        asked["vocab"] = cfg["vocab_slice"]
        return cfg["vocab_slice"]

    def serve_engine(cfg, eng, seed):
        asked["serve_engine"] = eng["toy_window"]
        return gpt2.serve_engine(cfg, eng, seed)

    def make_weights(cfg, lo, hi):
        asked["make_weights"] = True
        return gpt2.make_weights(cfg, lo, hi)

    def step_facts(engine, sched):
        return dict(gpt2.step_facts(engine, sched),
                    toy_slots=len(sched.active))

    def held_bytes(cfg, eng, decode_calls):
        asked["held_bytes"] = len(decode_calls)
        return gpt2.held_bytes(cfg, eng, decode_calls)

    class Reference:
        def __init__(self, cfg, width, control):
            self.inner = gpt2.serve_reference(cfg, width, control)

        def gaps(self, w, prompts, streams):
            asked["largest_prompt_id"] = max(max(p) for p in prompts)
            return self.inner.gaps(w, prompts, streams)

    def serve_reference(cfg, width, control=False):
        asked["serve_reference"] = width
        return Reference(cfg, width, control)

    def decode_step_flops(cfg, contexts, toy_slots):
        asked["counts"] = True
        assert toy_slots == len(contexts)
        return gpt2.counts.decode_step_flops(cfg, contexts)

    vars(toy).update(
        seed_key=gpt2.seed_key, vocab=vocab, serve_engine=serve_engine,
        make_weights=make_weights, step_facts=step_facts,
        held_bytes=held_bytes, serve_reference=serve_reference,
        counts=types.SimpleNamespace(decode_step_flops=decode_step_flops))
    return toy


def toy_reader(seen):
    """A reader of its own: the decode steps' FLOPs by the family's counts,
    and a look at what ``facts`` carries for it."""
    reader = types.ModuleType("benchmark.readers.toy_flops")

    def read(facts, params):
        seen["family"] = facts["family"].__name__
        seen["toy_slots"] = [step["toy_slots"]
                             for _, _, step in facts["decode_calls"]]
        seen["prefill_tokens"] = [step["tokens"]
                                  for _, _, step in facts["prefill_calls"]]
        seen["registry"] = facts["registry"]
        needs = facts["family"].counts.decode_step_flops
        return sum(needs(facts["config"], **step)
                   for _, _, step in facts["decode_calls"]) * params["scale"]

    reader.read = read
    return reader


def write_json(path, data):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f)


def test_a_second_family_runs_as_new_files_only(tmp_path, monkeypatch,
                                                capsys):
    asked, seen = {}, {}
    monkeypatch.setitem(sys.modules, "benchmark.families.toy",
                        toy_family(asked))
    monkeypatch.setitem(sys.modules, "benchmark.readers.toy_flops",
                        toy_reader(seen))
    data = str(tmp_path / "data")
    config = harness.load_json(CELLS, "configs", "tiny-chat.json")
    write_json(os.path.join(data, "configs", "toy.json"),
               dict(config, family="toy", vocab_slice=500))
    cell = harness.load_json(CELLS, "workloads", "tiny.chat.json")
    cell["engine"]["toy_window"] = 16
    write_json(os.path.join(data, "workloads", "toy.chat.json"),
               dict(cell, config="toy"))
    metric = {"name": "toy_flops.serve", "unit": "flop", "better": "lower",
              "source": "program_counter", "layer": "toy", "moves":
              "tpot_p95_ms"}
    write_json(os.path.join(data, "metrics", "toy_flops.serve.json"),
               dict(metric, reader="toy_flops", params={"scale": 1.0}))
    manifest = str(tmp_path / "BENCHMARK.json")
    write_json(manifest, {
        "run_seconds": 1, "end_to_end": [],
        "per_layer": [dict(metric, workloads=["toy.chat"])]})

    capsys.readouterr()
    harness.main(["--workload", "toy.chat", "--data", data, "--manifest",
                  manifest, "--rehearse", "--seed", "3000000019",
                  "--seconds", "1.0", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    assert line["correct"] is True, line["compared"]
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["readers_with_a_value"] == ["toy_flops.serve"]
    # the family was asked for the model, the weights, the vocabulary, the
    # held bytes, the reference and the counts
    assert asked["serve_engine"] == 16 and asked["make_weights"]
    assert asked["vocab"] == 500 and asked["largest_prompt_id"] < 500
    assert asked["held_bytes"] > 0 and asked["serve_reference"] > 0
    assert asked["counts"]
    # the reader saw the family, its step_facts entry and the registry
    assert seen["family"] == "benchmark.families.toy"
    assert seen["toy_slots"] and max(seen["toy_slots"]) > 0
    assert len(seen["prefill_tokens"]) == line["attempted"]
    at = seen["registry"]
    assert list(at) == ["open", "trace_from", "trace_to", "close"]
    admitted = at["close"]["serve/admitted"] - at["open"]["serve/admitted"]
    assert 0 < admitted <= line["attempted"]
    assert at["trace_from"]["serve/decode_steps"] \
        <= at["trace_to"]["serve/decode_steps"] \
        <= at["close"]["serve/decode_steps"]


def test_a_configuration_without_a_family_is_an_error():
    with pytest.raises(KeyError):
        harness.load_family({"vocab_size": 7})
    with pytest.raises(ModuleNotFoundError):
        harness.load_family({"family": "no_such_family"})


# -- nothing of GPT-2 on the harness's side of the seam -----------------------

BEHIND_THE_SEAM = re.compile(
    r"n_embd|n_layer|n_head|n_positions|n_inner|GPTModel|GPTConfig"
    r"|benchmark\.reference|import reference")
DIRECT_COUNTS = re.compile(r'(?<!family\.)(?<!family"\]\.)\bcounts\.'
                           r'(?!peaks\b|roofline_seconds\b)\w+')
GENERIC = sorted(
    p for p in glob.glob(os.path.join(BENCH, "kinds", "*.py"))
    + glob.glob(os.path.join(BENCH, "readers", "*.py"))
    + glob.glob(os.path.join(BENCH, "tools", "*.py"))
    + [os.path.join(BENCH, "run.py")] if os.path.getsize(p))


@pytest.mark.parametrize("path", GENERIC,
                         ids=[os.path.relpath(p, BENCH) for p in GENERIC])
def test_the_generic_code_names_nothing_of_gpt2(path):
    with open(path) as f:
        text = f.read()
    assert BEHIND_THE_SEAM.findall(text) == []
    # of benchmark/counts.py the generic code calls the chip's table and
    # the roofline alone; a formula is reached through family.counts
    assert DIRECT_COUNTS.findall(text) == []


def test_the_families_directory_holds_the_gpt2_family():
    gpt2 = harness.load_family({"family": "gpt2"})
    for name in ("vocab", "seed_key", "make_weights", "serve_engine",
                 "step_facts", "held_bytes", "serve_reference", "trainer",
                 "to_trainer", "from_trainer", "train_reference"):
        assert callable(getattr(gpt2, name)), name
    for name in ("n_params", "train_flops_per_token", "flash_train_work",
                 "decode_step_flops", "prefill_flops", "paged_decode_work"):
        assert callable(getattr(gpt2.counts, name)), name
    for name in ("gpt2-medium", "gpt2-large"):
        cfg = harness.load_json(BENCH, "configs", name + ".json")
        assert harness.load_family(cfg) is gpt2, name
    # every configuration names a family that loads and answers what the
    # kinds of its cells ask of a family (``family.<name>`` in the kind)
    kinds = {}
    for root in (BENCH, CELLS):
        for path in glob.glob(os.path.join(root, "workloads", "*.json")):
            cell = harness.load_json(path)
            kinds.setdefault(cell["config"], set()).add(cell["kind"])
    for root in (BENCH, CELLS):
        for path in glob.glob(os.path.join(root, "configs", "*.json")):
            name = os.path.basename(path)[:-len(".json")]
            cfg = harness.load_json(path)
            family = harness.load_family(cfg)
            assert family.__name__ == "benchmark.families." + cfg["family"]
            assert kinds.get(name), name          # some cell runs it
            asked = {"counts"}
            for kind in kinds[name]:
                with open(os.path.join(BENCH, "kinds", kind + ".py")) as f:
                    asked |= set(re.findall(r"\bfamily\.(\w+)", f.read()))
            for attr in sorted(asked):
                assert hasattr(family, attr), (name, attr)
                assert attr == "counts" or callable(getattr(family, attr)), \
                    (name, attr)


# -- the tools still start on a tiny cell, through the family -----------------

@pytest.mark.parametrize("tool,argv,first_keys", [
    (calibrate, ["--workload", "tiny.pretrain", "--seeds", "5",
                 "--controls", "1", "--skip-program"],
     {"control_int8", "fault_half_batch"}),
    (sweep_rate, ["--workload", "tiny.chat", "--rates", "10,20",
                  "--seconds", "1"],
     {"rate_per_s", "tokens_per_s", "mean_active_slots"}),
    (poisson_tails, ["--workload", "tiny.chat", "--seeds", "5",
                     "--seconds", "1"],
     {"draws", "active_slots_mean", "filled_bytes_at_most"}),
], ids=["calibrate", "sweep_rate", "poisson_tails"])
def test_a_tool_starts_on_a_tiny_cell(capsys, tool, argv, first_keys):
    capsys.readouterr()
    tool.main(argv + ["--data", CELLS, "--rehearse"])
    lines = [json.loads(x) for x in
             capsys.readouterr().out.strip().splitlines()]
    assert lines and first_keys <= set(lines[0]), lines

"""The harness end to end at a size a test run can hold (``--rehearse``
skips only the look for a chip): sound runs come out correct, and the
timed path broken underneath — once for each fault a cell can have — and
the control (the reference in int8 for training, in fp8 for serving) come
out NOT correct."""

import json
import os

import jax
import numpy as np
import pytest

from benchmark import run as harness
from benchmark.kinds import serve, train
from benchmark.tools import calibrate

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cells")


def run_cell(capsys, cell, seed=3_000_000_019, seconds=1.0):
    capsys.readouterr()
    harness.main(["--workload", cell, "--data", CELLS, "--rehearse",
                  "--seed", str(seed), "--seconds", str(seconds)])
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", ["tiny.pretrain", "tiny.pretrain-zero-dp4",
                                  "tiny.chat"])
def test_sound_run_is_correct_and_reports_no_metric(capsys, cell):
    line = run_cell(capsys, cell)
    assert line["correct"] is True, line["compared"]
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "compared"
    for name, c in line["compared"].items():
        if name != "not_compared":
            assert c["value"] <= c["limit"], name


# -- the control: the reference in a lower precision, in the program's place --

def context(cell_name, seed):
    cell, config = harness.load_cell(CELLS, cell_name)
    return harness.quiet_context(cell, config, jax.devices()[:cell["chips"]],
                                 seed, 1.0, rehearse=True)


SEEDS = [11, 12, 2 ** 31 + 13]


@pytest.mark.parametrize("seed", SEEDS)
def test_the_int8_control_in_the_trainers_place_is_not_correct(seed):
    ctx = context("tiny.pretrain", seed)
    sides = calibrate.train_seed(ctx, controls=True, program=False)
    checks = {k: tuple(v) for k, v in sides["control_int8"].items()}
    compared, _, correct = harness.decide(checks, ctx.cell["limits"], 0)
    assert not correct
    c = compared["grad_diff_median"]
    assert c["value"] > c["limit"], compared


@pytest.mark.parametrize("seed", SEEDS)
def test_the_fp8_control_in_the_engines_place_is_not_correct(seed):
    ctx = context("tiny.chat", seed)
    got = calibrate.serve_seed(ctx, controls=True)
    limits = ctx.cell["limits"]
    program = {k: tuple(v) for k, v in got["program"].items()}
    assert harness.decide(program, limits, 0)[2], got
    # the control's readings under the names the program's are held by
    control = {k[len("control_"):]: tuple(v)
               for k, v in got["control_fp8"].items()}
    compared, _, correct = harness.decide(control, limits, 0)
    assert not correct
    c = compared["token_gap_mean"]
    assert c["value"] > c["limit"], compared


# -- the faults ---------------------------------------------------------------

def state_unchanged(monkeypatch):
    """A step that returns its state unchanged (the loss is still new)."""
    def step(self, batch):
        keep = jax.tree_util.tree_map(lambda x: x.copy(), self.state)
        loss, *_ = self.compiled(*self.state, *batch)
        self.state = keep
        self.step_index += 1
        self.losses.append(loss)
        return loss
    monkeypatch.setattr(train.Job, "step", step)


def half_batch(monkeypatch):
    """Half of the batch left out, the mean taken over the rest."""
    sound = train.Job.step

    def step(self, batch):
        half = [np.concatenate([x[: len(x) // 2]] * 2) for x in batch]
        return sound(self, half)
    monkeypatch.setattr(train.Job, "step", step)


def no_exchange(monkeypatch):
    """The exchange between chips left out: every rank keeps its own slice
    of its own gradient where the reduce-scatter summed all ranks'."""
    import apex_tpu.parallel.distributed as dist
    from apex_tpu.utils.vma import cast_to_vma

    def local_only(flat, axis_name):
        dp = jax.lax.axis_size(axis_name)
        n = flat.shape[0] // dp
        flat = cast_to_vma(flat, frozenset({axis_name}))
        return dp * jax.lax.dynamic_slice_in_dim(
            flat, jax.lax.axis_index(axis_name) * n, n)
    monkeypatch.setattr(dist, "reduce_scatter_grads", local_only)


def altered_token(monkeypatch):
    """A token altered where it is produced: every 7th decode step hands
    the scheduler another token for its first active slot."""
    sound = serve.Server._wrap

    def wrap(self):
        sound(self)
        decode, calls = self.engine.decode, [0]

        def bad_decode(tokens, temps, active=None, **kw):
            out = np.array(decode(tokens, temps, active, **kw))
            calls[0] += 1
            if calls[0] % 7 == 0 and active is not None and active.any():
                slot = int(np.flatnonzero(active)[0])
                out[slot] = (out[slot] + 1) % 1031
            return out
        self.engine.decode = bad_decode
    monkeypatch.setattr(serve.Server, "_wrap", wrap)


@pytest.mark.parametrize("fault,cell,caught_by", [
    (state_unchanged, "tiny.pretrain", "change_gap"),
    (half_batch, "tiny.pretrain", "grad_gap"),
    (no_exchange, "tiny.pretrain-zero-dp4", "grad_gap"),
    (altered_token, "tiny.chat", "token_gap_mean"),
])
def test_fault_in_the_timed_path_is_not_correct(capsys, monkeypatch, fault,
                                                cell, caught_by):
    fault(monkeypatch)
    line = run_cell(capsys, cell)
    assert line["correct"] is False
    c = line["compared"][caught_by]
    assert c["value"] > c["limit"], line["compared"]

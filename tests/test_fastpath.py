"""Compound fastpath preset + roofline bucket autotuning tests.

Covers this PR's contracts on the 8-virtual-CPU-device mesh:

- ``TrainConfig.fastpath()``: the declarative compound preset (ZeRO-1 +
  auto-bucketed DP + selective remat, SP/tp_comm_overlap only where the
  mesh/jax can carry them), its drift-proof equality with bench.py's
  declarative ``BENCH_TRAIN_CONFIGS`` record, and its loud refusal on
  non-ZeRO-capable optimizers;
- ``pyprof.tune_bucket_bytes`` / ``bucket_wire_ms``: monotone wire-time
  model, the smallest-fully-hideable decision rule, deterministic picks,
  and the LOUD fallback to ``DEFAULT_BUCKET_BYTES`` on unpriceable
  programs;
- ``ddp_bucket_bytes="auto"`` through ``GPTHybridTrainer``: resolved at
  construction, deterministically, stored back into the trainer's config
  (the ZeRO ``bucket_stamp`` layout contract) and surfaced as the
  ``ddp/auto_bucket_bytes`` gauge;
- the compound structural assertion (satellite): the fastpath trainer
  step's jaxpr holds exactly B data-axis reduce-scatters + B gathers,
  zero full-tree psums of the flat gradient, NO materialized padded flat
  vector (the backward-interleave contract), and zero fused
  all_gather/reduce_scatter inside the wired TP layers — the per-feature
  assertions from PRs 2/4, asserted together for the first time;
- fastpath numerics: the compound configuration reproduces the plain
  trainer's loss trajectory (the overlap machinery is a schedule, not a
  numerics change).
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jaxpr_utils import (count_eqns, eqn_axes, flat_materializations,
                          iter_eqns)
from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                             ParallelConfig, TrainConfig)
from apex_tpu.observability.costs import DeviceSpec
from apex_tpu.parallel.distributed import DEFAULT_BUCKET_BYTES
from apex_tpu.pyprof import bucket_wire_ms, tune_bucket_bytes
from apex_tpu.pyprof.tune import DEFAULT_CANDIDATES

SPEC = DeviceSpec("test", 200e12, 800.0, 50.0)


# ---------------------------------------------------------------------------
# the preset
# ---------------------------------------------------------------------------

def _cfg(tp=1, pp=1, dp=4, opt="adam", **model_kw):
    M, mb, seq = 2, 2, 8
    return TrainConfig(
        model=ModelConfig(name="gpt", vocab_size=64, hidden_size=32,
                          num_layers=2, num_attention_heads=4,
                          max_position_embeddings=seq, **model_kw),
        parallel=ParallelConfig(tensor_model_parallel_size=tp,
                                pipeline_model_parallel_size=pp),
        batch=BatchConfig(global_batch_size=M * mb * dp,
                          micro_batch_size=mb),
        optimizer=OptimizerConfig(name=opt, lr=1e-2, weight_decay=0.0),
        opt_level="O0")


def test_fastpath_preset_fields():
    fast = _cfg().fastpath()
    assert fast.optimizer.zero == 1
    assert fast.ddp_bucket_bytes == "auto"
    assert fast.model.remat_policy == "selective"
    # tp=1: no SP to turn on, on any jax
    assert not fast.model.sequence_parallel
    assert not fast.model.tp_comm_overlap
    # bucket grid overridable (the elastic child / dryrun pin it)
    assert _cfg().fastpath(bucket_bytes=4096).ddp_bucket_bytes == 4096
    # explicit receiver settings are kept, not clobbered — including a
    # hand-tuned bucket grid (a checkpoint-layout property) and the
    # deprecated remat=True spelling (means "full", not "selective")
    assert _cfg(remat_policy="full").fastpath().model.remat_policy == "full"
    import dataclasses
    pinned = dataclasses.replace(_cfg(), ddp_bucket_bytes=8 << 20)
    assert pinned.fastpath().ddp_bucket_bytes == 8 << 20
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("ignore", DeprecationWarning)
        assert _cfg(remat=True).fastpath().model.remat_policy == "full"


def test_fastpath_sp_gating_follows_capability():
    fast = _cfg(tp=2, pp=1, dp=2).fastpath()
    assert fast.model.sequence_parallel
    assert fast.model.tp_comm_overlap
    # pp>1 never carries SP
    fast_pp = _cfg(tp=2, pp=2, dp=1).fastpath()
    assert not fast_pp.model.sequence_parallel


def test_fastpath_rejects_non_zero_optimizer():
    with pytest.raises(ValueError, match="ZeRO-capable"):
        _cfg(opt="sgd").fastpath()


def test_fastpath_matches_bench_declarative_record():
    """bench.py's BENCH_TRAIN_CONFIGS['gpt_fast'] is the declarative
    record of the preset — it must apply to the same config fastpath()
    produces (capability-gated SP fields aside), so the table cannot
    drift from the preset."""
    import bench

    base = _cfg()
    from_table = bench._train_config_from_spec(
        {"model": {"vocab_size": 64, "hidden_size": 32, "num_layers": 2,
                   "num_attention_heads": 4, "max_position_embeddings": 8},
         "optimizer": {"name": "adam", "lr": 1e-2, "weight_decay": 0.0},
         "opt_level": "O0"},
        bench.BENCH_TRAIN_CONFIGS["gpt_fast"],
        parallel={"tensor_model_parallel_size": 1},
        batch={"global_batch_size": 16, "micro_batch_size": 2})
    fast = base.fastpath()
    assert from_table.optimizer.zero == fast.optimizer.zero == 1
    assert from_table.ddp_bucket_bytes == fast.ddp_bucket_bytes == "auto"
    assert from_table.model.remat_policy == fast.model.remat_policy \
        == "selective"


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------

def test_bucket_wire_ms_monotone():
    """The wire-time model: strictly increasing in bucket bytes (at fixed
    ring) and non-decreasing in ring size; zero wire at axis_size=1."""
    sizes = [1 << s for s in range(16, 27)]
    walls = [bucket_wire_ms(c, 4, SPEC) for c in sizes]
    assert all(b > a for a, b in zip(walls, walls[1:])), walls
    rings = [bucket_wire_ms(1 << 22, n, SPEC) for n in (2, 4, 8, 16)]
    assert all(b >= a for a, b in zip(rings, rings[1:])), rings
    assert bucket_wire_ms(1 << 22, 1, SPEC) == 0.0
    with pytest.raises(ValueError, match="positive"):
        bucket_wire_ms(0, 4, SPEC)


def test_tune_picks_smallest_fully_hideable():
    grad_bytes = 64 << 20
    picked = tune_bucket_bytes(grad_bytes=grad_bytes, axis_size=4,
                               spec=SPEC, hide_ms=50.0)
    assert picked in DEFAULT_CANDIDATES
    B = -(-grad_bytes // picked)
    assert bucket_wire_ms(picked, 4, SPEC) <= 50.0 / B
    # every smaller candidate was NOT fully hideable
    for c in DEFAULT_CANDIDATES:
        if c >= picked:
            break
        assert bucket_wire_ms(c, 4, SPEC) > 50.0 / (-(-grad_bytes // c))
    # a huge hide window: the smallest candidate wins outright (most
    # overlap edges at zero exposed wire)
    assert tune_bucket_bytes(grad_bytes=grad_bytes, axis_size=4,
                             spec=SPEC, hide_ms=1e6) \
        == min(DEFAULT_CANDIDATES)


def test_tune_is_deterministic_and_starved_pick_is_least_exposed():
    kw = dict(grad_bytes=256 << 20, axis_size=8, spec=SPEC, hide_ms=0.01)
    a, b = tune_bucket_bytes(**kw), tune_bucket_bytes(**kw)
    assert a == b and a in DEFAULT_CANDIDATES
    # nothing is hideable under 0.01 ms; the pick minimizes total
    # exposed wire across the ladder
    def exposed(c):
        B = -(-(256 << 20) // c)
        return B * (bucket_wire_ms(c, 8, SPEC) - 0.01 / B)
    assert all(exposed(a) <= exposed(c) + 1e-12
               for c in DEFAULT_CANDIDATES)


def test_tune_falls_back_loudly_on_unpriceable():
    for kw in (dict(program=None, grad_bytes=4 << 20, axis_size=4),
               dict(grad_bytes=0, axis_size=4),
               dict(grad_bytes=4 << 20, axis_size=4, hide_ms=0.0),
               dict(program=object(), grad_bytes=4 << 20, axis_size=4)):
        with pytest.warns(UserWarning, match="DEFAULT_BUCKET_BYTES"):
            assert tune_bucket_bytes(**kw) == DEFAULT_BUCKET_BYTES


def test_tune_prices_a_real_program():
    """The program path: a traced fwd+bwd prices to a positive hide
    window and resolves without the fallback warning."""
    w = jnp.ones((64, 64), jnp.float32)
    x = jnp.ones((8, 64), jnp.float32)

    def fwd_bwd(w, x):
        return jax.grad(lambda w: jnp.sum(jnp.tanh(x @ w) ** 2))(w)

    traced = jax.jit(fwd_bwd).trace(w, x)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        picked = tune_bucket_bytes(traced, grad_bytes=8 << 20,
                                   axis_size=4, spec=SPEC)
    assert picked in DEFAULT_CANDIDATES


# ---------------------------------------------------------------------------
# "auto" through the trainer
# ---------------------------------------------------------------------------

def test_trainer_resolves_auto_deterministically():
    from apex_tpu.observability.registry import get_registry
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    cfg = _cfg().fastpath()          # ddp_bucket_bytes == "auto"
    mesh = cfg.initialize_mesh(devices=jax.devices()[:4])
    try:
        tr1 = GPTHybridTrainer(cfg, mesh)
        tr2 = GPTHybridTrainer(cfg, mesh)
        assert isinstance(tr1.bucket_bytes, int)
        assert tr1.bucket_bytes == tr2.bucket_bytes
        # the resolved grid is stored back into the config — sidecars
        # and bucket_stamp both see the concrete int, never "auto"
        assert tr1.cfg.ddp_bucket_bytes == tr1.bucket_bytes
        assert tr1.opt.bucket_bytes == tr1.bucket_bytes
        g = get_registry().gauge("ddp/auto_bucket_bytes")
        assert g.is_set and g.value == float(tr1.bucket_bytes)
        # the ZeRO layout stamp a freshly-built state would carry is the
        # resolved grid (cheap check — no init compile; the stamp's
        # restore-boundary behavior is covered in test_dp_overlap)
        assert int(tr1.opt._stamp()) == tr1.bucket_bytes
    finally:
        parallel_state.destroy_model_parallel()


def test_trainer_rejects_bogus_bucket_spelling():
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    import dataclasses
    cfg = dataclasses.replace(_cfg(), ddp_bucket_bytes="4MiB")
    mesh = cfg.initialize_mesh(devices=jax.devices()[:4])
    try:
        with pytest.raises(ValueError, match='"auto"'):
            GPTHybridTrainer(cfg, mesh)
    finally:
        parallel_state.destroy_model_parallel()


def test_build_optimizer_refuses_unresolved_auto():
    import dataclasses
    cfg = dataclasses.replace(_cfg().fastpath())
    with pytest.raises(ValueError, match="resolved before"):
        cfg.build_optimizer()


# ---------------------------------------------------------------------------
# the compound structural assertion (satellite: PRs 2/4 asserted together)
# ---------------------------------------------------------------------------

def _compound_jaxpr_checks(tp, dp):
    from apex_tpu.optimizers._flatten import bucket_bounds
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    bb = 1024
    cfg = _cfg(tp=tp, pp=1, dp=dp).fastpath(bucket_bytes=bb)
    M, mb, seq = 2, 2, 8
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, (M, dp * mb, seq)))
    targets = jnp.asarray(rng.randint(0, 64, (M, dp * mb, seq)))
    mesh = cfg.initialize_mesh(devices=jax.devices()[: tp * dp])
    try:
        tr = GPTHybridTrainer(cfg, mesh)
        # abstract state: the structural assertions only need avals, so
        # nothing in this test compiles or executes
        state = jax.eval_shape(tr.init_state, jax.random.PRNGKey(0))
        lay = tr.opt._layout
        assert lay is not None
        bounds = bucket_bounds(lay, bb)
        B = len(bounds)
        assert B > 1
        jaxpr = jax.make_jaxpr(tr.train_step)(*state, tokens, targets)

        def data_axis(eqn):
            return "data" in eqn_axes(eqn)

        # PR-4 contract, on the COMPOUND program: B data-axis
        # reduce-scatters, B per-bucket gathers (invariant all_gather or
        # the documented psum fallback), no full-tree psum
        n_rs = count_eqns(jaxpr, "reduce_scatter", where=data_axis)
        assert n_rs == B, (n_rs, B)
        n_ag = count_eqns(jaxpr, "all_gather", where=data_axis) \
            + count_eqns(jaxpr, "all_gather_invariant", where=data_axis)
        sizes = {n for _, n in bounds}
        n_fallback = count_eqns(
            jaxpr, "psum", where=lambda e: data_axis(e) and any(
                v.aval.ndim == 1 and v.aval.size in sizes
                for v in e.invars))
        assert n_ag == B or n_fallback >= B, (n_ag, n_fallback, B)
        assert count_eqns(
            jaxpr, "psum", where=lambda e: data_axis(e) and any(
                v.aval.ndim == 1 and v.aval.size == lay.padded
                for v in e.invars)) == 0
        # the backward-interleave contract: the padded flat vector never
        # materializes anywhere in the compound step
        flat_outs = flat_materializations(jaxpr.jaxpr, lay.padded)
        assert not flat_outs, flat_outs
        # PR-2 contract on the same program: zero fused
        # all_gather/reduce_scatter INSIDE the wired TP layers (their
        # named_scope regions) — at tp>1 with overlap on, the rings
        # replaced them; the data-axis ZeRO collectives above are
        # outside these scopes by construction
        wired = ("tp_column_linear", "tp_row_linear")
        fused_in_layers = [
            eqn.primitive.name for eqn in iter_eqns(jaxpr.jaxpr)
            if eqn.primitive.name in ("all_gather", "reduce_scatter")
            and any(w in str(eqn.source_info.name_stack) for w in wired)]
        assert not fused_in_layers, fused_in_layers
        if tp > 1 and cfg.model.tp_comm_overlap:
            # the rings are really there (tp-1 hops per ring, scanned)
            assert count_eqns(jaxpr, "ppermute") > 0
        return cfg
    finally:
        parallel_state.destroy_model_parallel()


def test_fastpath_compound_jaxpr_tp2():
    """The full compound assertion at tp=2 x dp=4: the preset carries
    SP+tp_comm_overlap and the TP-layer scopes must hold zero fused
    collectives next to the B-bucket ZeRO structure — every per-feature
    assertion from PRs 2/4 holds on ONE program. (The tp=1 shape of the
    same checks runs in the multichip dryrun gate's fastpath leg.)"""
    cfg = _compound_jaxpr_checks(tp=2, dp=4)
    assert cfg.model.tp_comm_overlap


# ---------------------------------------------------------------------------
# the bench leg
# ---------------------------------------------------------------------------

def test_bench_gpt_fast_smoke(monkeypatch):
    """bench_gpt_fast end to end on the 8-virtual-device mesh with
    shrunken shapes: both trainer legs compile and run, the emitted line
    carries the A/B ratio, the resolved auto bucket grid, and a config
    block of real field names."""
    import bench

    monkeypatch.setattr(bench, "_RESULTS", [])
    monkeypatch.setitem(
        bench.BENCH_TRAIN_CONFIGS, "gpt_base",
        {"model": {"name": "gpt", "vocab_size": 64, "hidden_size": 32,
                   "num_layers": 2, "num_attention_heads": 4,
                   "max_position_embeddings": 8},
         "optimizer": {"name": "adam", "lr": 1e-3},
         "opt_level": "O0"})
    bench.bench_gpt_fast(iters=2, warmup=1, mb=2, seq=8, max_devices=2)
    line = bench._RESULTS[-1]
    assert line["metric"] == "gpt_fast_tokens_per_sec"
    assert line["unit"] == "tokens/sec" and line["value"] > 0
    assert line["vs_baseline"] > 0 and line["base_tps"] > 0
    cfg = line["config"]
    assert cfg["model"]["remat_policy"] == "selective"
    assert cfg["optimizer"]["zero"] == 1
    assert isinstance(cfg["ddp_bucket_bytes"], int)  # "auto" resolved


# ---------------------------------------------------------------------------
# numerics: the compound configuration is a schedule, not a math change
# ---------------------------------------------------------------------------

def test_fastpath_parity_with_plain_trainer():
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    M, mb, seq, dp = 2, 2, 8, 2
    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, 64, (M, dp * mb, seq)))
    targets = jnp.asarray(rng.randint(0, 64, (M, dp * mb, seq)))

    def run(cfg, steps=2):
        mesh = cfg.initialize_mesh(devices=jax.devices()[:dp])
        try:
            tr = GPTHybridTrainer(cfg, mesh)
            state = tr.init_state(jax.random.PRNGKey(0))
            step = jax.jit(tr.train_step)
            losses = []
            for _ in range(steps):
                loss, *state = step(*state, tokens, targets)
                losses.append(float(loss))
            return losses, state
        finally:
            parallel_state.destroy_model_parallel()

    l_ref, s_ref = run(_cfg(dp=dp))
    l_fast, s_fast = run(_cfg(dp=dp).fastpath(bucket_bytes=1024))
    np.testing.assert_allclose(l_fast, l_ref, rtol=1e-6, atol=1e-7)
    # Two Adam steps at lr 1e-2 move an entry by up to 2e-2; the params
    # are held to a thousandth of that. Tighter is not the schedule's to
    # give: where an entry's gradient is itself rounding noise (|g| ~ eps
    # = 1e-8: biases that start at zero) the normalised step
    # m / (sqrt(v) + eps) turns the last bits of g, which a bucketed
    # reduce-scatter sums in another order, into any step up to lr.
    for pa, pb in zip(jax.tree_util.tree_leaves((s_ref[0], s_ref[1])),
                      jax.tree_util.tree_leaves((s_fast[0], s_fast[1]))):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=3e-6, atol=2e-5)

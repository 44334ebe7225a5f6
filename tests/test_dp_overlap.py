"""Bucketed DP gradient sync + ZeRO-through-the-trainer tests.

Covers the overlap PR's contracts on the 8-virtual-CPU-device mesh:

- the bucket grid (``optimizers/_flatten.bucket_bounds``) is exact:
  covering, ordered, shard-divisible;
- the bucketed allreduce (``parallel/distributed.py``) matches the
  per-leaf path numerically and compiles to exactly B psums;
- ``accumulate_gradients`` windows fire B bucket psums (vs one per leaf),
  and its new guards (empty window, unbound axis) raise loudly;
- trainer-level ZeRO parity: ``zero=1`` reproduces the replicated
  ``FusedAdam`` trainer bit-for-bit-to-tolerance, with the jaxpr holding
  exactly B data-axis reduce-scatters and B gathers, and no full-tree
  psum of the flat gradient;
- ``zero=off`` + bucketing-off is provably the pre-bucketing program
  (no reduce_scatter / bucket machinery in the jaxpr; old-style config
  dicts round-trip);
- ``jit_train_step`` donation aliases the state buffers and leaves
  numerics unchanged;
- the new ``ddp/*`` / ``zero/*`` metrics surface through
  ``train_step_with_metrics``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from _jaxpr_utils import (collective_census, count_eqns, eqn_axes,
                          jaxpr_str)
from apex_tpu.optimizers._flatten import bucket_bounds, build_layout
from apex_tpu.parallel import DistributedDataParallel, allreduce_grads
from jax import shard_map


def _mesh(n=None):
    devs = jax.devices() if n is None else jax.devices()[:n]
    return Mesh(np.array(devs), ("data",))


def _grad_tree(seed=0):
    rng = np.random.RandomState(seed)
    return {"w": jnp.asarray(rng.randn(8, 100, 7), jnp.float32),
            "b": jnp.asarray(rng.randn(8, 13), jnp.float32),
            "emb": jnp.asarray(rng.randn(8, 5, 16), jnp.float32)}


# ---------------------------------------------------------------------------
# bucket grid
# ---------------------------------------------------------------------------

def test_bucket_bounds_cover_and_divide():
    lay = build_layout({"a": jnp.zeros(1000), "b": jnp.zeros(23)}, chunks=4)
    for bb in (4, 256, 1024, 10 ** 9):
        bounds = bucket_bounds(lay, bb)
        # covering, ordered, disjoint
        off = 0
        for o, n in bounds:
            assert o == off and n > 0
            assert n % 4 == 0  # every bucket reduce-scatters over 4 ranks
            off += n
        assert off == lay.padded
    # None = monolithic single span
    assert bucket_bounds(lay, None) == ((0, lay.padded),)
    with pytest.raises(ValueError, match="positive"):
        bucket_bounds(lay, 0)


def test_ravel_span_unravel_parts_roundtrip():
    """Span-local ravel/unravel (the backward-interleave building blocks)
    are element-identical to the monolithic ravel/unravel over any
    bucket grid — including scalar leaves, dtype casts, and the padding
    tail."""
    from apex_tpu.optimizers._flatten import (bucket_bounds, build_layout,
                                              ravel, ravel_span, unravel,
                                              unravel_parts)

    rng = np.random.RandomState(3)
    tree = {"w": jnp.asarray(rng.randn(7, 5), jnp.float32),
            "s": jnp.asarray(1.5, jnp.float32),
            "z": jnp.zeros((0,), jnp.float32),   # zero-size leaf
            "h": jnp.asarray(rng.randn(9), jnp.bfloat16)}
    lay = build_layout(tree, chunks=4)
    assert lay.padded > lay.total  # the padding tail is exercised
    flat = np.asarray(ravel(tree, lay))
    for bb in (16, 40, 1 << 20, None):
        bounds = bucket_bounds(lay, bb)
        parts = [ravel_span(tree, lay, o, n) for o, n in bounds]
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(p) for p in parts]), flat)
        ref = unravel(jnp.asarray(flat), lay)
        got = unravel_parts(parts, bounds, lay)
        for a, b in zip(jax.tree_util.tree_leaves(ref),
                        jax.tree_util.tree_leaves(got)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))
    with pytest.raises(ValueError, match="outside"):
        ravel_span(tree, lay, lay.padded - 2, 4)
    with pytest.raises(ValueError, match="parts"):
        unravel_parts([flat[:4]], ((0, 4), (4, lay.padded - 4)), lay)
    with pytest.raises(ValueError, match="cover"):
        unravel_parts([jnp.asarray(flat[:4])], ((0, 4),), lay)
    with pytest.raises(ValueError, match="tile"):
        unravel_parts([jnp.asarray(flat[:4]), jnp.asarray(flat[8:])],
                      ((0, 4), (8, lay.padded - 8)), lay)


def test_build_layout_is_memoized_with_identical_jaxpr():
    """Satellite: the FlatLayout is cached across steps/calls (the
    per-call rebuild was measurable host overhead at 512 leaves), and
    the cached path traces a byte-identical program."""
    from apex_tpu.optimizers import FlatOptimizer, FusedAdam
    from apex_tpu.optimizers._flatten import (build_layout,
                                              clear_layout_cache,
                                              layout_cache_stats,
                                              segment_ids)

    clear_layout_cache()
    tree = {f"p{i}": jnp.ones((4, 3), jnp.float32) for i in range(5)}
    l1 = build_layout(tree, chunks=2)
    l2 = build_layout(tree, chunks=2)
    assert l1 is l2  # the hit returns the identical object
    stats = layout_cache_stats()
    assert stats["hits"] == 1 and stats["misses"] == 1
    assert build_layout(tree, chunks=4) is not l1  # chunks key in the id
    np.testing.assert_array_equal(np.asarray(segment_ids(l1)),
                                  np.asarray(segment_ids(l1)))

    def step_txt():
        opt = FlatOptimizer(FusedAdam(lr=1e-3))
        state = opt.init(tree)
        grads = jax.tree_util.tree_map(jnp.ones_like, tree)
        return jaxpr_str(lambda g, s, p: opt._step(g, s, p),
                         grads, state, tree)

    clear_layout_cache()
    cold = step_txt()             # builds the layout
    warm = step_txt()             # second optimizer, cache warm
    assert layout_cache_stats()["hits"] >= 1
    assert cold == warm           # cached path is program-identical
    clear_layout_cache()


# ---------------------------------------------------------------------------
# bucketed allreduce
# ---------------------------------------------------------------------------

def _run_allreduce(grads, mesh, **kw):
    def inner(w, b, emb):
        return allreduce_grads({"w": w, "b": b, "emb": emb}, "data", **kw)
    return shard_map(inner, mesh=mesh,
                     in_specs=(P("data"), P("data"), P("data")),
                     out_specs=P("data"))


def test_bucketed_allreduce_matches_per_leaf():
    mesh = _mesh()
    g = _grad_tree()
    args = (g["w"], g["b"], g["emb"])
    ref = jax.jit(_run_allreduce(g, mesh))(*args)
    for bb in (512, 4096, 1 << 20):
        out = jax.jit(_run_allreduce(g, mesh, bucket_bytes=bb))(*args)
        for k in ref:
            np.testing.assert_allclose(np.asarray(out[k]),
                                       np.asarray(ref[k]),
                                       rtol=1e-6, atol=1e-6)


def test_bucketed_allreduce_predivide_numerics():
    mesh = _mesh()
    g = _grad_tree(1)
    args = (g["w"], g["b"], g["emb"])
    plain = jax.jit(_run_allreduce(g, mesh, bucket_bytes=512))(*args)
    pre = jax.jit(_run_allreduce(g, mesh, bucket_bytes=512,
                                 gradient_predivide_factor=8.0))(*args)
    for k in plain:
        np.testing.assert_allclose(np.asarray(pre[k]),
                                   np.asarray(plain[k]),
                                   rtol=1e-5, atol=1e-6)


def test_bucketed_allreduce_jaxpr_holds_b_psums():
    """The bucketing is real: exactly B psums, no fused all-reduce of the
    whole tree, one per bucket of the flat layout."""
    mesh = _mesh()
    g = _grad_tree()
    lay = build_layout(
        {k: v[0] for k, v in g.items()}, chunks=1)
    args = (g["w"], g["b"], g["emb"])
    from _jaxpr_utils import flat_materializations
    for bb in (512, 1600):
        B = len(bucket_bounds(lay, bb))
        assert B > 1
        # one trace serves both assertions
        jaxpr = jax.make_jaxpr(_run_allreduce(g, mesh, bucket_bytes=bb))(
            *args)
        assert str(jaxpr).count("psum") == B, (bb, B)
        # span-local assembly: the full padded flat vector never
        # materializes — each bucket ravels from its own leaves only
        assert not flat_materializations(jaxpr.jaxpr, lay.padded)
    # a bucket larger than the whole tree degenerates to ONE flat psum
    txt = jaxpr_str(_run_allreduce(g, mesh, bucket_bytes=1 << 20), *args)
    assert txt.count("psum") == 1
    # and the per-leaf path: one psum per leaf
    txt = jaxpr_str(_run_allreduce(g, mesh), *args)
    assert txt.count("psum") == 3


def test_bucketed_allreduce_rejects_groups():
    from apex_tpu.parallel import Reducer

    with pytest.raises(ValueError, match="mutually exclusive"):
        allreduce_grads({"w": jnp.zeros(4)}, "data",
                        axis_index_groups=[[0, 1]], bucket_bytes=512)
    with pytest.raises(ValueError, match="mutually exclusive"):
        Reducer("data", axis_index_groups=[[0, 1]], bucket_bytes=512)


def test_bucketed_reducer_matches_pmean():
    from apex_tpu.parallel import Reducer

    mesh = _mesh()
    tree = {"a": jnp.arange(8 * 40, dtype=jnp.float32).reshape(8, 40),
            "b": jnp.ones((8, 3), jnp.float32)}

    def run(red):
        return jax.jit(shard_map(
            lambda a, b: red.reduce({"a": a, "b": b}),
            mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P("data")))(tree["a"], tree["b"])

    ref = run(Reducer("data"))
    out = run(Reducer("data", bucket_bytes=64))
    for k in ref:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                   rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# DDP + accumulation window
# ---------------------------------------------------------------------------

def test_accumulate_gradients_bucketed_window():
    """A bucketed DDP fires B bucket psums once per window (not per
    microbatch) and reproduces the per-leaf window grads."""
    from apex_tpu.training import accumulate_gradients

    mesh = _mesh()
    rng = np.random.RandomState(6)
    K = 3
    params = {"w1": jnp.asarray(rng.randn(4, 33), jnp.float32),
              "w2": jnp.asarray(rng.randn(33, 2), jnp.float32)}
    xs = jnp.asarray(rng.randn(K, 16, 4), jnp.float32)
    ys = jnp.asarray(rng.randn(K, 16, 2), jnp.float32)

    def loss_fn(p, mb):
        x, y = mb
        return jnp.mean((jnp.tanh(x @ p["w1"]) @ p["w2"] - y) ** 2)

    def run(ddp):
        def inner(p, xs, ys):
            _, grads = accumulate_gradients(ddp, loss_fn, p, (xs, ys))
            return grads
        def wrapped(p, xs, ys):
            return shard_map(
                inner, mesh=mesh,
                in_specs=(P(), P(None, "data"), P(None, "data")),
                out_specs=P())(p, xs, ys)
        return wrapped

    bb = 256
    lay = build_layout(params, chunks=1)
    B = len(bucket_bounds(lay, bb))
    assert B > 1
    mono = run(DistributedDataParallel("data", delay_allreduce=True))
    buck = run(DistributedDataParallel("data", delay_allreduce=True,
                                       bucket_bytes=bb))
    assert jaxpr_str(mono, params, xs, ys).count("psum") == 2  # per leaf
    assert jaxpr_str(buck, params, xs, ys).count("psum") == B
    g_m = jax.jit(mono)(params, xs, ys)
    g_b = jax.jit(buck)(params, xs, ys)
    for k in params:
        np.testing.assert_allclose(np.asarray(g_b[k]), np.asarray(g_m[k]),
                                   rtol=1e-6, atol=1e-6)


def test_accumulate_gradients_empty_window_raises():
    from apex_tpu.training import accumulate_gradients

    ddp = DistributedDataParallel("data", delay_allreduce=True)
    with pytest.raises(ValueError, match="num_micro == 0"):
        accumulate_gradients(ddp, lambda p, mb: jnp.sum(p),
                             jnp.zeros((2, 2)), jnp.zeros((0, 4)))


def test_accumulate_gradients_unbound_axis_raises():
    from apex_tpu.training import accumulate_gradients

    ddp = DistributedDataParallel("nonexistent_axis", delay_allreduce=True)
    with pytest.raises(ValueError, match="is not bound"):
        accumulate_gradients(ddp, lambda p, mb: jnp.sum(p),
                             jnp.zeros((2, 2)), jnp.zeros((3, 4)))


# ---------------------------------------------------------------------------
# trainer-level ZeRO parity + program shape (satellite + acceptance)
# ---------------------------------------------------------------------------

DP = 4


def _trainer_cfg(zero=False, bucket_bytes=None):
    from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                                 ParallelConfig, TrainConfig)
    M, mb, seq = 2, 2, 8
    return TrainConfig(
        model=ModelConfig(name="gpt", vocab_size=64, hidden_size=32,
                          num_layers=2, num_attention_heads=4,
                          max_position_embeddings=seq),
        parallel=ParallelConfig(tensor_model_parallel_size=1,
                                pipeline_model_parallel_size=1),
        batch=BatchConfig(global_batch_size=M * mb * DP,
                          micro_batch_size=mb),
        optimizer=OptimizerConfig(name="adam", lr=1e-2, weight_decay=0.0,
                                  zero=zero),
        opt_level="O0", ddp_bucket_bytes=bucket_bytes)


def _trainer_data(seed=0):
    rng = np.random.RandomState(seed)
    M, mb, seq = 2, 2, 8
    return (jnp.asarray(rng.randint(0, 64, (M, DP * mb, seq))),
            jnp.asarray(rng.randint(0, 64, (M, DP * mb, seq))))


def _run_trainer(cfg, steps=3):
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    tokens, targets = _trainer_data()
    mesh = cfg.initialize_mesh(devices=jax.devices()[:DP])
    try:
        tr = GPTHybridTrainer(cfg, mesh)
        state = tr.init_state(jax.random.PRNGKey(0))
        step = jax.jit(tr.train_step)
        losses = []
        for _ in range(steps):
            loss, *state = step(*state, tokens, targets)
            losses.append(float(loss))
        return tr, losses, state
    finally:
        parallel_state.destroy_model_parallel()


def test_trainer_zero_parity_with_replicated_adam():
    """zero=1 on the dp=4 mesh: loss trajectory and post-3-step params
    match the replicated FusedAdam trainer. The ZeRO update math is the
    same fp32 elementwise program over a flat view; the only reassociation
    is reduce_scatter's ring order vs psum's, so tolerance is a few ULPs
    (documented; bit-identity holds on this mesh in practice for the loss,
    asserted exactly)."""
    _, l_ref, s_ref = _run_trainer(_trainer_cfg(zero=False))
    _, l_z, s_z = _run_trainer(_trainer_cfg(zero=1, bucket_bytes=1024))
    assert l_ref == l_z, (l_ref, l_z)
    for pa, pb in zip(jax.tree_util.tree_leaves((s_ref[0], s_ref[1])),
                      jax.tree_util.tree_leaves((s_z[0], s_z[1]))):
        np.testing.assert_allclose(np.asarray(pa), np.asarray(pb),
                                   rtol=3e-6, atol=3e-6)


def test_trainer_zero_jaxpr_per_bucket_collectives():
    """The bucketed ZeRO step holds exactly B data-axis reduce-scatters and
    B gathers — and no full-tree psum of the flat gradient (the monolithic
    pattern this PR removes)."""
    from apex_tpu.optimizers._flatten import bucket_bounds as bbounds
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    bb = 1024
    cfg = _trainer_cfg(zero=1, bucket_bytes=bb)
    tokens, targets = _trainer_data()
    mesh = cfg.initialize_mesh(devices=jax.devices()[:DP])
    try:
        tr = GPTHybridTrainer(cfg, mesh)
        state = tr.init_state(jax.random.PRNGKey(0))
        lay = tr.opt._layout
        assert lay is not None  # init traced the layout
        B = len(bbounds(lay, bb))
        assert B > 1

        def data_axis(eqn):
            return "data" in eqn_axes(eqn)

        jaxpr = jax.make_jaxpr(tr.train_step)(*state, tokens, targets)
        n_rs = count_eqns(jaxpr, "reduce_scatter", where=data_axis)
        assert n_rs == B, (n_rs, B)
        # gather leg: B invariant gathers where this jax has them, else the
        # documented psum fallback (utils/vma.invariant_all_gather) — B
        # bucket-sized psums either way, never one padded-size reduction
        n_ag = count_eqns(
            jaxpr, "all_gather", where=data_axis) + count_eqns(
            jaxpr, "all_gather_invariant", where=data_axis)
        sizes = {n for _, n in bbounds(lay, bb)}

        def is_flat_psum(eqn):
            return data_axis(eqn) and any(
                v.aval.size == lay.padded and v.aval.ndim == 1
                for v in eqn.invars)

        n_fallback = count_eqns(
            jaxpr, "psum", where=lambda e: data_axis(e) and any(
                v.aval.ndim == 1 and v.aval.size in sizes
                for v in e.invars))
        assert n_ag == B or n_fallback >= B, (n_ag, n_fallback, B)
        # no monolithic full-tree psum of the flat gradient
        assert count_eqns(jaxpr, "psum", where=is_flat_psum) == 0
    finally:
        parallel_state.destroy_model_parallel()


def test_trainer_zero_off_unbucketed_is_pre_pr_program():
    """zero=off + bucketing off: the step jaxpr carries no reduce_scatter
    and no bucket machinery, and is identical to a trainer built from an
    old-style config dict that predates the new fields — the same
    provably-unchanged contract as health level="off"."""
    from apex_tpu.config import TrainConfig
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    cfg = _trainer_cfg(zero=False, bucket_bytes=None)
    d = cfg.to_dict()
    # a config dict from before this PR: no ddp_bucket_bytes, bool zero
    del d["ddp_bucket_bytes"]
    assert d["optimizer"]["zero"] is False
    old_cfg = TrainConfig.from_dict(d)
    tokens, targets = _trainer_data()
    mesh = cfg.initialize_mesh(devices=jax.devices()[:DP])
    try:
        tr = GPTHybridTrainer(cfg, mesh)
        tr_old = GPTHybridTrainer(old_cfg, mesh)
        state = tr.init_state(jax.random.PRNGKey(0))
        args = state + (tokens, targets)
        txt = jaxpr_str(tr.train_step, *args)
        assert collective_census(txt)["reduce_scatter"] == 0
        assert jaxpr_str(tr_old.train_step, *args) == txt
    finally:
        parallel_state.destroy_model_parallel()


def test_config_zero_spellings():
    from apex_tpu.config import OptimizerConfig, TrainConfig
    from apex_tpu.optimizers import DistributedFusedAdam, FusedAdam

    def build(z):
        return TrainConfig(
            optimizer=OptimizerConfig(name="adam", zero=z)).build_optimizer()

    for z in (False, 0, "off"):
        assert isinstance(build(z), FusedAdam)
    for z in (True, 1, "1"):
        assert isinstance(build(z), DistributedFusedAdam)
    with pytest.raises(ValueError, match="zero"):
        build("2")
    # bucket size threads from the train config into the ZeRO optimizer
    opt = TrainConfig(
        optimizer=OptimizerConfig(name="adam", zero=1),
        ddp_bucket_bytes=4096).build_optimizer()
    assert opt.bucket_bytes == 4096


def test_trainer_zero_rejects_mismatched_restored_state():
    """The restored-checkpoint boundary: a ZeRO state trained under one
    ddp_bucket_bytes entering jit_train_step of a trainer configured with
    another fails loudly before dispatch (the bucket-major shard order
    would otherwise be silently permuted)."""
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    tokens, targets = _trainer_data()
    cfg_a = _trainer_cfg(zero=1, bucket_bytes=None)
    mesh = cfg_a.initialize_mesh(devices=jax.devices()[:DP])
    try:
        state = GPTHybridTrainer(cfg_a, mesh).init_state(
            jax.random.PRNGKey(0))
    finally:
        parallel_state.destroy_model_parallel()
    cfg_b = _trainer_cfg(zero=1, bucket_bytes=2048)
    mesh = cfg_b.initialize_mesh(devices=jax.devices()[:DP])
    try:
        tr_b = GPTHybridTrainer(cfg_b, mesh)
        with pytest.raises(ValueError, match="bucket_bytes"):
            tr_b.jit_train_step()(*state, tokens, targets)
    finally:
        parallel_state.destroy_model_parallel()


# ---------------------------------------------------------------------------
# donated state buffers (perf satellite)
# ---------------------------------------------------------------------------

def test_jit_train_step_donates_state():
    """jit_train_step aliases stage_stack/shared/opt_state into their
    outputs (input_output_alias in the compiled module) so the live-buffer
    high-water drops by a state generation; numerics are unchanged."""
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    cfg = _trainer_cfg(zero=False)
    tokens, targets = _trainer_data()
    mesh = cfg.initialize_mesh(devices=jax.devices()[:DP])
    try:
        tr = GPTHybridTrainer(cfg, mesh)
        state = tr.init_state(jax.random.PRNGKey(0))
        args = state + (tokens, targets)
        plain = jax.jit(tr.train_step).lower(*args).compile()
        donated = tr.jit_train_step().lower(*args).compile()
        assert "input_output_alias" not in plain.as_text()
        assert "input_output_alias" in donated.as_text()
        # the aliasing must cover the whole donated state, not one buffer:
        # every stage/shared/opt_state leaf has an alias entry
        n_state_leaves = len(jax.tree_util.tree_leaves(state[:3]))
        n_aliases = donated.as_text().count("may-alias")
        assert n_aliases >= n_state_leaves, (n_aliases, n_state_leaves)
        # live-buffer math: peak-ish footprint is args + outputs + temps
        # minus bytes the runtime reuses via aliasing — donation must
        # cover (almost) the whole donated state and shrink the total
        ma_p, ma_d = plain.memory_analysis(), donated.memory_analysis()
        if ma_p is not None and ma_d is not None:
            state_bytes = sum(
                l.size * l.dtype.itemsize
                for l in jax.tree_util.tree_leaves(state[:3]))
            assert ma_p.alias_size_in_bytes == 0
            assert ma_d.alias_size_in_bytes >= 0.9 * state_bytes

            def live(ma):
                return (ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes - ma.alias_size_in_bytes)

            assert live(ma_d) <= live(ma_p)
        loss_p, *out_p = plain(*args)
        # donated call consumes its args: pass fresh copies
        fresh = jax.tree_util.tree_map(jnp.copy, state)
        loss_d, *out_d = donated(*fresh, tokens, targets)
        assert float(loss_p) == float(loss_d)
        for a, b in zip(jax.tree_util.tree_leaves(out_p),
                        jax.tree_util.tree_leaves(out_d)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        parallel_state.destroy_model_parallel()


# ---------------------------------------------------------------------------
# telemetry surface
# ---------------------------------------------------------------------------

def test_bucketing_metrics_surface():
    from apex_tpu.optimizers._flatten import bucket_bounds as bbounds
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    bb = 1024
    tokens, targets = _trainer_data()

    # ZeRO leg: reduce-scatter + shard metrics
    cfg = _trainer_cfg(zero=1, bucket_bytes=bb)
    mesh = cfg.initialize_mesh(devices=jax.devices()[:DP])
    try:
        tr = GPTHybridTrainer(cfg, mesh)
        state = tr.init_state(jax.random.PRNGKey(0))
        *_, metrics = jax.jit(tr.train_step_with_metrics)(
            *state, tokens, targets)
        got = metrics.as_floats()
        lay = tr.opt._layout
        B = len(bbounds(lay, bb))
        assert got["ddp/num_buckets"] == float(B)
        assert got["ddp/reduce_scatter_bytes"] > 0
        assert got["zero/shard_bytes"] == float(4 * lay.chunk)
        assert got["ddp/bucket_bytes"] == float(
            4 * max(n for _, n in bbounds(lay, bb)))
    finally:
        parallel_state.destroy_model_parallel()

    # replicated leg: bucketed allreduce metrics
    cfg = _trainer_cfg(zero=False, bucket_bytes=bb)
    mesh = cfg.initialize_mesh(devices=jax.devices()[:DP])
    try:
        tr = GPTHybridTrainer(cfg, mesh)
        state = tr.init_state(jax.random.PRNGKey(0))
        *_, metrics = jax.jit(tr.train_step_with_metrics)(
            *state, tokens, targets)
        got = metrics.as_floats()
        assert got["ddp/num_buckets"] >= 2
        assert got["ddp/allreduce_bytes"] > 0
    finally:
        parallel_state.destroy_model_parallel()


def test_jit_train_step_verify_donation_self_check():
    """jit_train_step(verify_donation=True): the first dispatch runs the
    analysis engine's jaxpr-donation rule on the compiled step (every
    donated leaf aliased, no double-donated buffer) and then dispatches
    through the verified executable (PR 11)."""
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    cfg = _trainer_cfg(zero=True)
    tokens, targets = _trainer_data()
    mesh = cfg.initialize_mesh(devices=jax.devices()[:DP])
    try:
        tr = GPTHybridTrainer(cfg, mesh)
        state = tr.init_state(jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="donate=True"):
            tr.jit_train_step(donate=False, verify_donation=True)
        step = tr.jit_train_step(verify_donation=True)
        loss1, *state = step(*state, tokens, targets)
        loss2, *_ = step(*state, tokens, targets)  # verified executable
        assert np.isfinite(float(loss1)) and np.isfinite(float(loss2))
    finally:
        parallel_state.destroy_model_parallel()

"""Transformer parallel toolkit tests on the 8-device CPU mesh.

Models: ``reference:tests/L0/run_transformer/`` — ``test_parallel_state.py``,
``test_mapping.py``, ``test_layers.py``, ``test_cross_entropy.py``,
``test_data.py``, ``test_random.py``, ``test_microbatches.py``,
``test_pipeline_parallel_fwd_bwd.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.pipeline_parallel import (
    ConstantNumMicroBatches, RampupBatchsizeNumMicroBatches,
    forward_backward_no_pipelining,
    forward_backward_pipelining_without_interleaving,
    forward_backward_pipelining_with_interleaving,
    get_forward_backward_func, get_ltor_masks_and_position_ids,
    pipelined_apply)


@pytest.fixture
def mesh_tp2_pp2():
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2, pipeline_model_parallel_size=2)
    yield mesh
    parallel_state.destroy_model_parallel()


@pytest.fixture
def mesh_tp4():
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=4)
    yield mesh
    parallel_state.destroy_model_parallel()


@pytest.fixture
def mesh_pp4():
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size=4)
    yield mesh
    parallel_state.destroy_model_parallel()


# ---------------------------------------------------------------------------
# parallel_state (test_parallel_state.py)
# ---------------------------------------------------------------------------

def test_parallel_state_sizes_and_groups(mesh_tp2_pp2):
    assert parallel_state.get_tensor_model_parallel_world_size() == 2
    assert parallel_state.get_pipeline_model_parallel_world_size() == 2
    assert parallel_state.get_data_parallel_world_size() == 2
    # group membership matches reference rank math (tp fastest, dp, pp)
    assert parallel_state.get_tensor_model_parallel_groups() == [
        [0, 1], [2, 3], [4, 5], [6, 7]]
    assert parallel_state.get_data_parallel_groups() == [
        [0, 2], [1, 3], [4, 6], [5, 7]]
    assert parallel_state.get_pipeline_model_parallel_groups() == [
        [0, 4], [1, 5], [2, 6], [3, 7]]
    assert parallel_state.get_embedding_ranks() == [
        [0, 4], [1, 5], [2, 6], [3, 7]]


def test_parallel_state_validation():
    with pytest.raises(RuntimeError):
        parallel_state.initialize_model_parallel(tensor_model_parallel_size=3)
    with pytest.raises(RuntimeError):
        parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2,
            virtual_pipeline_model_parallel_size=2)
    assert not parallel_state.model_parallel_is_initialized()


# ---------------------------------------------------------------------------
# mappings (test_mapping.py)
# ---------------------------------------------------------------------------

def test_mappings_roundtrip_and_grads(mesh_tp4):
    mesh = parallel_state.get_mesh()
    x = jnp.asarray(np.random.RandomState(0).randn(4, 8), jnp.float32)

    def body(x):
        # scatter then gather is identity (test_mapping.py parity); the
        # gathered value is device-varying-but-equal, so cross the shard_map
        # boundary with a pmean (no-op on equal values)
        s = tp.scatter_to_tensor_model_parallel_region(x)
        g = tp.gather_from_tensor_model_parallel_region(s)
        return jax.lax.pmean(g, "tensor")

    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P(), out_specs=P()))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x), rtol=1e-6)

    # copy fwd is identity; bwd is psum: grad of sum over ranks = tp * ones
    def loss(x):
        def inner(x):
            y = tp.copy_to_tensor_model_parallel_region(x)
            return jax.lax.psum(jnp.sum(y), "tensor") / 4.0
        return shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P())(x)

    g = jax.jit(jax.grad(loss))(x)
    np.testing.assert_allclose(np.asarray(g), np.ones_like(x), rtol=1e-6)


# ---------------------------------------------------------------------------
# TP layers (test_layers.py): sharded == unsharded
# ---------------------------------------------------------------------------

def test_column_row_parallel_linear_match_dense(mesh_tp4):
    mesh = parallel_state.get_mesh()
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(6, 16), jnp.float32)

    col = tp.ColumnParallelLinear(16, 32, gather_output=True)
    row = tp.RowParallelLinear(32, 16, input_is_parallel=False)
    cp = col.init(jax.random.PRNGKey(0))
    rp = row.init(jax.random.PRNGKey(1))

    def fwd(cp, rp, x):
        def inner(cp, rp, x):
            h, _ = col(cp, x)
            out, _ = row(rp, h)
            # varying-but-equal (per-rank bias copies); pmean to cross out
            return jax.lax.pmean(out, "tensor")
        return shard_map(
            inner, mesh=mesh,
            in_specs=(P("tensor"), P("tensor"), P()), out_specs=P())(cp, rp, x)

    out = jax.jit(fwd)(cp, rp, x)

    # dense reference from the full stacked weights
    w_col = np.asarray(cp["weight"]).reshape(32, 16)
    b_col = np.asarray(cp["bias"]).reshape(32)
    w_row = np.concatenate(list(np.asarray(rp["weight"])), axis=1)  # (16,32)
    b_row = np.asarray(rp["bias"])[0]
    ref = np.asarray(x) @ w_col.T + b_col
    ref = ref @ w_row.T + b_row
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4, atol=1e-4)

    # grads flow through both layers
    def loss(cp, rp):
        return jnp.sum(fwd(cp, rp, x) ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1)))(cp, rp)
    for leaf in jax.tree_util.tree_leaves(g):
        assert np.isfinite(np.asarray(leaf)).all()


def test_row_parallel_grads_match_dense(mesh_tp4):
    """TP=4 weight AND bias grads equal the dense (TP=1) grads on every rank
    (ADVICE r1: the bias copies used to receive grad/tp)."""
    mesh = parallel_state.get_mesh()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 16), jnp.float32)

    row = tp.RowParallelLinear(16, 8, input_is_parallel=False, world_size=4)
    params = row.init(jax.random.PRNGKey(0))
    params = {"weight": params["weight"], "bias": params["bias"] + 0.3}

    def loss_tp(params, x):
        y, _ = row(params, x)
        return jnp.sum(y ** 2)

    def run(params, x):
        def inner(params, x):
            l, g = jax.value_and_grad(loss_tp)(params, x)
            return jax.lax.pmean(l, "tensor"), g
        specs = {"weight": P("tensor"), "bias": P("tensor")}
        return shard_map(inner, mesh=mesh, in_specs=(specs, P()),
                         out_specs=(P(), specs))(params, x)

    l, g = jax.jit(run)(params, x)

    w_full = jnp.concatenate([params["weight"][i] for i in range(4)], axis=1)
    b_full = params["bias"][0]

    def loss_dense(w, b, x):
        return jnp.sum((x @ w.T + b) ** 2)

    ld, (gw, gb) = jax.value_and_grad(loss_dense, argnums=(0, 1))(
        w_full, b_full, x)
    np.testing.assert_allclose(float(l), float(ld), rtol=1e-5)
    for i in range(4):
        # every replicated bias copy gets the FULL dense grad, not grad/tp
        np.testing.assert_allclose(np.asarray(g["bias"][i]), np.asarray(gb),
                                   rtol=1e-5)
    gw_tp = jnp.concatenate([g["weight"][i] for i in range(4)], axis=1)
    np.testing.assert_allclose(np.asarray(gw_tp), np.asarray(gw),
                               rtol=1e-4, atol=1e-4)


def test_vocab_parallel_embedding(mesh_tp4):
    mesh = parallel_state.get_mesh()
    emb = tp.VocabParallelEmbedding(64, 16)
    ep = emb.init(jax.random.PRNGKey(2))
    ids = jnp.asarray(np.random.RandomState(3).randint(0, 64, (4, 10)))

    out = jax.jit(shard_map(
        lambda p, i: jax.lax.pmean(emb(p, i), "tensor"), mesh=mesh,
        in_specs=(P("tensor"), P()), out_specs=P()))(ep, ids)

    full = np.asarray(ep["weight"]).reshape(64, 16)
    np.testing.assert_allclose(np.asarray(out), full[np.asarray(ids)],
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# vocab-parallel cross entropy (test_cross_entropy.py)
# ---------------------------------------------------------------------------

def test_vocab_parallel_cross_entropy_vs_torch(mesh_tp4):
    mesh = parallel_state.get_mesh()
    rng = np.random.RandomState(4)
    logits = rng.randn(5, 7, 32).astype(np.float32)
    target = rng.randint(0, 32, (5, 7))

    # shard logits along vocab: (5,7,32) -> per-rank (5,7,8)
    def run(logits, target):
        return shard_map(
            lambda l, t: tp.vocab_parallel_cross_entropy(l, t),
            mesh=mesh, in_specs=(P(None, None, "tensor"), P()),
            out_specs=P())(logits, target)

    loss = jax.jit(run)(jnp.asarray(logits), jnp.asarray(target))
    ref = torch.nn.functional.cross_entropy(
        torch.tensor(logits).reshape(-1, 32), torch.tensor(target).reshape(-1),
        reduction="none").reshape(5, 7)
    np.testing.assert_allclose(np.asarray(loss), ref.numpy(), rtol=1e-5,
                               atol=1e-5)

    # grads match dense softmax-CE
    def j_loss(l):
        return jnp.sum(run(l, jnp.asarray(target)))

    g = jax.jit(jax.grad(j_loss))(jnp.asarray(logits))
    tl = torch.tensor(logits, requires_grad=True)
    torch.nn.functional.cross_entropy(
        tl.reshape(-1, 32), torch.tensor(target).reshape(-1),
        reduction="sum").backward()
    np.testing.assert_allclose(np.asarray(g), tl.grad.numpy(), rtol=1e-4,
                               atol=1e-5)


# ---------------------------------------------------------------------------
# data broadcast (test_data.py)
# ---------------------------------------------------------------------------

def test_broadcast_data(mesh_tp4):
    mesh = parallel_state.get_mesh()
    # rank-varying input: only rank 0's survives
    data = jnp.arange(4 * 3, dtype=jnp.float32).reshape(4, 3)

    def body(x):
        # x arrives sharded over tensor: each rank has (1, 3) — its "own" data
        out = tp.broadcast_data(["k"], {"k": x})["k"]
        return out

    out = jax.jit(shard_map(
        body, mesh=mesh, in_specs=P("tensor"), out_specs=P("tensor")))(data)
    # every rank's slot now holds rank 0's row
    np.testing.assert_allclose(np.asarray(out),
                               np.tile(np.asarray(data[0:1]), (4, 1)),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# RNG (test_random.py)
# ---------------------------------------------------------------------------

def test_rng_tracker_semantics():
    tp.model_parallel_seed(1234, tensor_rank=0)
    tracker = tp.get_rng_tracker()
    states0 = tracker.get_states()
    with tracker.fork() as key_a:
        pass
    with tracker.fork() as key_b:
        pass
    assert not np.array_equal(np.asarray(key_a), np.asarray(key_b))
    # restore replays the stream
    tracker.set_states(states0)
    with tracker.fork() as key_a2:
        pass
    np.testing.assert_array_equal(np.asarray(key_a), np.asarray(key_a2))
    # tp ranks get distinct streams; same seed reproduces
    tp.model_parallel_seed(1234, tensor_rank=1)
    with tp.get_rng_tracker().fork() as key_r1:
        pass
    assert not np.array_equal(np.asarray(key_a), np.asarray(key_r1))
    with pytest.raises(Exception):
        tp.get_rng_tracker().add("default", 1)
    with pytest.raises(Exception):
        tp.get_rng_tracker().make_key("nonexistent")


# ---------------------------------------------------------------------------
# microbatches (test_microbatches.py)
# ---------------------------------------------------------------------------

def test_microbatch_calculators():
    const = ConstantNumMicroBatches(64, 2, 4)
    assert const.get() == 8
    ramp = RampupBatchsizeNumMicroBatches(
        start_batch_size=8, batch_size_increment=8, ramup_samples=80,
        global_batch_size=32, micro_batch_size=2, data_parallel_size=2)
    assert ramp.get() == 2  # 8/(2*2)
    ramp.update(40, False)
    assert ramp.get_current_global_batch_size() == 16
    ramp.update(1000, False)
    assert ramp.get() == 8  # 32/(2*2)


# ---------------------------------------------------------------------------
# pipeline schedules (test_pipeline_parallel_fwd_bwd.py)
# ---------------------------------------------------------------------------

def _stage_fn(chunk_params, x, stage_idx):
    """Uniform affine stage: y = tanh(x @ w + b)."""
    return jnp.tanh(x @ chunk_params["w"] + chunk_params["b"])


def test_pipelined_apply_matches_sequential(mesh_pp4):
    mesh = parallel_state.get_mesh()
    rng = np.random.RandomState(5)
    d = 8
    # per-stage params, stacked (pp=4, d, d)
    ws = jnp.asarray(rng.randn(4, d, d) * 0.3, jnp.float32)
    bs = jnp.asarray(rng.randn(4, d) * 0.1, jnp.float32)
    micro = jnp.asarray(rng.randn(6, 2, d), jnp.float32)  # M=6, mb=2

    def run(ws, bs, micro):
        def inner(ws, bs, micro):
            # local stage params arrive sharded: (1, d, d) -> chunk axis
            params = {"w": ws[0][None], "b": bs[0][None]}
            params = jax.tree_util.tree_map(lambda p: p, params)
            out = pipelined_apply(
                lambda cp, x, s: _stage_fn(
                    {"w": cp["w"], "b": cp["b"]}, x, s),
                {"w": ws, "b": bs}, micro, num_chunks=1)
            # conservatively varying-but-equal over data/tensor: pmean out
            return jax.lax.pmean(jax.lax.pmean(out, "data"), "tensor")
        return shard_map(
            inner, mesh=mesh,
            in_specs=(P("pipe"), P("pipe"), P()), out_specs=P())(ws, bs, micro)

    out = jax.jit(run)(ws, bs, micro)

    # sequential reference
    ref = np.asarray(micro)
    for s in range(4):
        ref = np.tanh(ref @ np.asarray(ws[s]) + np.asarray(bs[s]))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-5)


def test_pipeline_fwd_bwd_matches_no_pipelining(mesh_pp4):
    """All three schedules produce the same loss and equivalent grads
    (the cross-schedule consistency the reference test sweeps)."""
    mesh = parallel_state.get_mesh()
    rng = np.random.RandomState(6)
    d = 8
    ws = jnp.asarray(rng.randn(4, d, d) * 0.3, jnp.float32)
    bs = jnp.asarray(rng.randn(4, d) * 0.1, jnp.float32)
    micro = jnp.asarray(rng.randn(6, 2, d), jnp.float32)
    targets = jnp.asarray(rng.randn(6, 2, d), jnp.float32)

    def loss_fn_of(targets):
        def loss_fn(y, m):
            t = jax.lax.dynamic_index_in_dim(targets, m, 0, keepdims=False)
            return jnp.mean((y - t) ** 2)
        return loss_fn

    # pipelined over pipe axis
    def run_pipe(ws, bs):
        def inner(ws, bs):
            loss, grads = forward_backward_pipelining_without_interleaving(
                _stage_fn, micro, {"w": ws[0], "b": bs[0]},
                loss_fn=loss_fn_of(targets))
            pm = lambda x: jax.lax.pmean(jax.lax.pmean(x, "data"), "tensor")
            return pm(loss), jax.tree_util.tree_map(pm, grads)
        return shard_map(inner, mesh=mesh, in_specs=(P("pipe"), P("pipe")),
                         out_specs=(P(), P("pipe")))(ws, bs)

    loss_pipe, grads_pipe = jax.jit(run_pipe)(ws, bs)

    # sequential reference: no pipelining, full model on one device
    def full_model(params, mb):
        x, t = mb
        for s in range(4):
            x = _stage_fn({"w": params["w"][s], "b": params["b"][s]}, x, s)
        return jnp.mean((x - t) ** 2)

    loss_ref, grads_ref = forward_backward_no_pipelining(
        full_model, (micro, targets), {"w": ws, "b": bs})

    np.testing.assert_allclose(float(loss_pipe), float(loss_ref), rtol=1e-5)
    # out_specs=P("pipe") concatenates per-stage grads on axis 0
    np.testing.assert_allclose(
        np.asarray(grads_pipe["w"]).reshape(4, d, d),
        np.asarray(grads_ref["w"]), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grads_pipe["b"]).reshape(4, d),
        np.asarray(grads_ref["b"]), rtol=1e-4, atol=1e-5)


def test_interleaved_schedule(mesh_pp4):
    """vpp=2: 8 global stages round-robin over 4 devices; must equal the
    sequential 8-layer model."""
    mesh = parallel_state.get_mesh()
    rng = np.random.RandomState(7)
    d = 8
    # global stage g = c*4 + dev -> device holds chunks stacked on axis 0
    ws_global = jnp.asarray(rng.randn(8, d, d) * 0.2, jnp.float32)
    bs_global = jnp.asarray(rng.randn(8, d) * 0.1, jnp.float32)
    micro = jnp.asarray(rng.randn(5, 2, d), jnp.float32)
    targets = jnp.asarray(rng.randn(5, 2, d), jnp.float32)

    # rearrange to (dev, chunk, ...): dev d gets stages [d, d+4]
    ws_dev = jnp.stack([jnp.stack([ws_global[c * 4 + dev] for c in range(2)])
                        for dev in range(4)])
    bs_dev = jnp.stack([jnp.stack([bs_global[c * 4 + dev] for c in range(2)])
                        for dev in range(4)])

    def loss_fn(y, m):
        t = jax.lax.dynamic_index_in_dim(targets, m, 0, keepdims=False)
        return jnp.mean((y - t) ** 2)

    def run(ws, bs):
        def inner(ws, bs):
            loss, grads = forward_backward_pipelining_with_interleaving(
                _stage_fn, micro, {"w": ws[0], "b": bs[0]},
                loss_fn=loss_fn, num_model_chunks=2)
            pm = lambda x: jax.lax.pmean(jax.lax.pmean(x, "data"), "tensor")
            return pm(loss), jax.tree_util.tree_map(pm, grads)
        return shard_map(inner, mesh=mesh, in_specs=(P("pipe"), P("pipe")),
                         out_specs=(P(), P("pipe")))(ws, bs)

    loss_pipe, grads = jax.jit(run)(ws_dev, bs_dev)

    # sequential reference
    def full_model(params, mb):
        x, t = mb
        for g in range(8):
            x = _stage_fn({"w": params["w"][g], "b": params["b"][g]}, x, g)
        return jnp.mean((x - t) ** 2)

    loss_ref, grads_ref = forward_backward_no_pipelining(
        full_model, (micro, targets), {"w": ws_global, "b": bs_global})
    np.testing.assert_allclose(float(loss_pipe), float(loss_ref), rtol=1e-5)
    # grads: out_specs P("pipe") stacks per-device chunk grads, so entry
    # [dev*2 + c] is global stage c*4 + dev — must match the sequential ref
    gw = np.asarray(grads["w"]).reshape(4, 2, d, d)
    gb = np.asarray(grads["b"]).reshape(4, 2, d)
    for dev in range(4):
        for c in range(2):
            g = c * 4 + dev
            np.testing.assert_allclose(
                gw[dev, c], np.asarray(grads_ref["w"])[g],
                rtol=1e-4, atol=1e-5)
            np.testing.assert_allclose(
                gb[dev, c], np.asarray(grads_ref["b"])[g],
                rtol=1e-4, atol=1e-5)


def test_get_forward_backward_func_dispatch():
    assert get_forward_backward_func(None, 1) is forward_backward_no_pipelining
    assert (get_forward_backward_func(None, 4)
            is forward_backward_pipelining_without_interleaving)
    assert (get_forward_backward_func(2, 4)
            is forward_backward_pipelining_with_interleaving)


def test_ltor_masks_and_position_ids():
    data = jnp.asarray([[5, 1, 9, 1, 3]])  # eod=1
    mask, loss_mask, pos = get_ltor_masks_and_position_ids(
        data, eod_token=1, reset_position_ids=True,
        reset_attention_mask=True, eod_mask_loss=True)
    # loss masked at eod positions
    np.testing.assert_array_equal(np.asarray(loss_mask[0]),
                                  [1, 0, 1, 0, 1])
    # position ids reset after eod: docs are [5,1], [9,1], [3]
    np.testing.assert_array_equal(np.asarray(pos[0]), [0, 1, 0, 1, 0])
    # attention cannot cross document boundaries: pos 2 can't see pos 0
    assert bool(mask[0, 0, 2, 0])
    assert not bool(mask[0, 0, 3, 2])


def test_dispatch_uniform_call_shape():
    """The dispatcher's pp=1 branch accepts the pipelined call shape."""
    rng = np.random.RandomState(9)
    d = 8
    params = {"w": jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32),
              "b": jnp.zeros(d)}
    micro = jnp.asarray(rng.randn(3, 2, d), jnp.float32)
    targets = jnp.asarray(rng.randn(3, 2, d), jnp.float32)

    def loss_fn(y, m):
        t = jax.lax.dynamic_index_in_dim(targets, m, 0, keepdims=False)
        return jnp.mean((y - t) ** 2)

    f = get_forward_backward_func(None, 1)
    loss, grads = f(_stage_fn, micro, params, loss_fn=loss_fn)
    # direct reference
    def full(params, mb):
        x, t = mb
        return jnp.mean((_stage_fn(params, x, 0) - t) ** 2)
    loss_ref, grads_ref = forward_backward_no_pipelining(
        full, (micro, targets), params)
    np.testing.assert_allclose(float(loss), float(loss_ref), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               np.asarray(grads_ref["w"]), rtol=1e-5)


def test_gpt_pipelined_embedding_and_tied_head(mesh_pp4):
    """The full-model pipeline decomposition (embedding on stage 0, final
    LN + tied logits + LM loss on the last stage) reproduces the single-chip
    GPT loss AND grads — including the tied embedding's grad, which receives
    both the stage-0 lookup contribution and the last-stage logit
    contribution via the pipe-axis psum (the reference's embedding-group
    allreduce, ``reference:apex/transformer/parallel_state.py:215-247``)."""
    from apex_tpu.models import GPTConfig, GPTModel

    mesh = parallel_state.get_mesh()
    PP, M, mb, seq = 4, 8, 2, 8
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=4,
                    num_attention_heads=4, max_position_embeddings=seq,
                    compute_dtype=jnp.float32, use_flash=False)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, (M, mb, seq)))
    targets = jnp.asarray(rng.randint(0, 64, (M, mb, seq)))

    stage, embed_fn, head_fn, split_params, shared_of = model.pipeline_fns(
        PP, targets)
    stage_stack = split_params(params)      # leaves (PP, per, ...)
    shared = shared_of(params)

    def run_pipe(stage_stack, shared):
        def inner(stage_stack, shared):
            my_stage = jax.tree_util.tree_map(lambda p: p[0], stage_stack)
            loss, (sg, shg) = \
                forward_backward_pipelining_without_interleaving(
                    stage, tokens, my_stage, loss_fn=head_fn,
                    shared_params=shared, embed_fn=embed_fn)
            pm = lambda x: jax.lax.pmean(jax.lax.pmean(x, "data"), "tensor")
            sg = jax.tree_util.tree_map(lambda g: pm(g)[None], sg)
            return pm(loss), sg, jax.tree_util.tree_map(pm, shg)
        spec = jax.tree_util.tree_map(lambda _: P("pipe"), stage_stack)
        shspec = jax.tree_util.tree_map(lambda _: P(), shared)
        return shard_map(inner, mesh=mesh,
                         in_specs=(spec, shspec),
                         out_specs=(P(), spec, shspec))(stage_stack, shared)

    loss_pipe, stage_grads, shared_grads = jax.jit(run_pipe)(
        stage_stack, shared)

    # single-chip reference: same loss = mean over microbatches
    def ref_loss(params):
        losses = jax.vmap(
            lambda tok, tgt: model.loss(params, tok, tgt))(tokens, targets)
        return jnp.mean(losses)

    loss_ref, grads_ref = jax.value_and_grad(ref_loss)(params)

    np.testing.assert_allclose(float(loss_pipe), float(loss_ref), rtol=2e-5)
    # layer grads: pipelined (PP, per, ...) vs reference (num_layers, ...)
    ref_layers = split_params(grads_ref)
    for a, b in zip(jax.tree_util.tree_leaves(stage_grads),
                    jax.tree_util.tree_leaves(ref_layers)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)
    # shared grads: embedding (tied: lookup + logits contributions) + final ln
    ref_shared = shared_of(grads_ref)
    for (ka, a), b in zip(
            jax.tree_util.tree_leaves_with_path(shared_grads),
            jax.tree_util.tree_leaves(ref_shared)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5,
            err_msg=str(ka))
    # the tied embedding grad must actually mix both contributions: it is
    # nonzero (lookup path) and differs from an untied-head run's grad
    emb = np.asarray(shared_grads["embedding"]["word"]["weight"])
    assert np.abs(emb).max() > 0


def test_stage_predicates_with_explicit_virtual_rank():
    """Virtual-chunk predicates take the chunk index explicitly (traced or
    host); the module-global remains reference-API compat only."""
    mesh = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size=4,
        virtual_pipeline_model_parallel_size=2)
    try:
        def inner():
            first = parallel_state.is_pipeline_first_stage(virtual_rank=0)
            not_first = parallel_state.is_pipeline_first_stage(
                virtual_rank=1)
            last = parallel_state.is_pipeline_last_stage(virtual_rank=1)
            not_last = parallel_state.is_pipeline_last_stage(virtual_rank=0)
            return tuple(
                jnp.reshape(v.astype(jnp.int32), (1,))
                for v in (first, not_first, last, not_last))

        outs = shard_map(inner, mesh=mesh, in_specs=(),
                         out_specs=(P("pipe"),) * 4)()
        first, not_first, last, not_last = (np.asarray(o) for o in outs)
        assert first.tolist() == [1, 0, 0, 0]
        assert not_first.tolist() == [0, 0, 0, 0]
        assert last.tolist() == [0, 0, 0, 1]
        assert not_last.tolist() == [0, 0, 0, 0]
    finally:
        parallel_state.destroy_model_parallel()


def test_gpt_interleaved_pipeline_with_embedding_head(mesh_pp4):
    """Virtual-pipeline (vpp=2) GPT with the pipelined embedding + tied
    head: Megatron chunk layout (chunk c on device d = global stage
    c*S + d), loss and shared grads matching single-chip."""
    from apex_tpu.models import GPTConfig, GPTModel

    mesh = parallel_state.get_mesh()
    S, VPP, M, mb, seq = 4, 2, 8, 2, 8
    L = S * VPP  # one layer per global stage
    cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=L,
                    num_attention_heads=4, max_position_embeddings=seq,
                    compute_dtype=jnp.float32, use_flash=False)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.RandomState(3)
    tokens = jnp.asarray(rng.randint(0, 64, (M, mb, seq)))
    targets = jnp.asarray(rng.randint(0, 64, (M, mb, seq)))

    stage, embed_fn, head_fn, split_params, shared_of = model.pipeline_fns(
        L, targets)
    # (L, per=1, ...) -> (VPP, S, per, ...): axis 1 shards over pipe
    chunked = jax.tree_util.tree_map(
        lambda p: p.reshape(VPP, S, *p.shape[1:]), split_params(params))
    shared = shared_of(params)

    def run(chunked, shared):
        def inner(chunked, shared):
            mine = jax.tree_util.tree_map(lambda p: p[:, 0], chunked)
            loss, (sg, shg) = forward_backward_pipelining_with_interleaving(
                stage, tokens, mine, loss_fn=head_fn,
                num_model_chunks=VPP, shared_params=shared,
                embed_fn=embed_fn)
            pm = lambda x: jax.lax.pmean(jax.lax.pmean(x, "data"), "tensor")
            sg = jax.tree_util.tree_map(lambda g: pm(g)[:, None], sg)
            return pm(loss), sg, jax.tree_util.tree_map(pm, shg)
        spec = jax.tree_util.tree_map(lambda _: P(None, "pipe"), chunked)
        shspec = jax.tree_util.tree_map(lambda _: P(), shared)
        return shard_map(inner, mesh=mesh, in_specs=(spec, shspec),
                         out_specs=(P(), spec, shspec))(chunked, shared)

    loss_pipe, chunk_grads, shared_grads = jax.jit(run)(chunked, shared)

    def ref_loss(params):
        return jnp.mean(jax.vmap(
            lambda tok, tgt: model.loss(params, tok, tgt))(tokens, targets))

    loss_ref, grads_ref = jax.value_and_grad(ref_loss)(params)
    np.testing.assert_allclose(float(loss_pipe), float(loss_ref), rtol=2e-5)

    # chunk grads back to (L, ...) layer order: global stage g = c*S + d
    for a, b in zip(jax.tree_util.tree_leaves(chunk_grads),
                    jax.tree_util.tree_leaves(
                        split_params(grads_ref))):
        a = np.asarray(a)           # (VPP, S, per, ...)
        a = a.reshape(L, *a.shape[2:])
        np.testing.assert_allclose(a, np.asarray(b), rtol=5e-4, atol=5e-5)
    for (ka, a), b in zip(
            jax.tree_util.tree_leaves_with_path(shared_grads),
            jax.tree_util.tree_leaves(shared_of(grads_ref))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=str(ka))

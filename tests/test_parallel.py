"""Data-parallel layer tests on the 8-virtual-device CPU mesh.

Models: ``reference:tests/distributed/synced_batchnorm/`` (single vs multi
device parity, uneven batches via groups, fused relu),
``tests/distributed/DDP/ddp_race_condition_test.py`` (grad-value identities),
``examples/simple/distributed``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P
from _jaxpr_utils import jaxpr_str
from jax import shard_map

from apex_tpu.parallel import (
    DistributedDataParallel, Reducer, SyncBatchNorm, allreduce_grads,
    convert_syncbn_model, create_syncbn_process_group, sync_batch_norm)


def _mesh():
    return Mesh(np.array(jax.devices()), ("data",))


def test_allreduce_grads_matches_manual_mean():
    mesh = _mesh()
    grads = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)

    @jax.jit
    def run(g):
        return shard_map(
            lambda g: allreduce_grads({"w": g}, "data")["w"],
            mesh=mesh, in_specs=P("data"), out_specs=P("data"))(g)

    out = run(grads)
    expected = np.tile(np.asarray(grads).mean(0, keepdims=True), (8, 1))
    np.testing.assert_allclose(np.asarray(out), expected, rtol=1e-6)


def test_predivide_factor_numerics():
    """predivide path must equal plain averaging in exact arithmetic
    (distributed.py:445-454)."""
    mesh = _mesh()
    grads = jnp.asarray(np.random.RandomState(0).randn(8, 4), jnp.float32)

    def run(pre):
        return shard_map(
            lambda g: allreduce_grads(
                {"w": g}, "data", gradient_predivide_factor=pre)["w"],
            mesh=mesh, in_specs=P("data"), out_specs=P("data"))(grads)

    np.testing.assert_allclose(np.asarray(run(1.0)), np.asarray(run(8.0)),
                               rtol=1e-5)


def test_ddp_value_and_grad():
    mesh = _mesh()
    ddp = DistributedDataParallel(axis_name="data")
    x = jnp.asarray(np.random.RandomState(1).randn(16, 4), jnp.float32)
    y = jnp.asarray(np.random.RandomState(2).randn(16, 1), jnp.float32)
    w = jnp.zeros((4, 1), jnp.float32)

    def loss_fn(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    @jax.jit
    def dist_grad(w, x, y):
        return shard_map(
            lambda w, x, y: ddp.value_and_grad(loss_fn)(w, x, y)[1],
            mesh=mesh, in_specs=(P(), P("data"), P("data")),
            out_specs=P())(w, x, y)

    g_dist = dist_grad(w, x, y)
    g_ref = jax.grad(loss_fn)(w, x, y)
    np.testing.assert_allclose(np.asarray(g_dist), np.asarray(g_ref), rtol=1e-5)


def test_reducer_averages_params():
    mesh = _mesh()
    params = jnp.arange(8.0).reshape(8, 1)
    out = jax.jit(shard_map(
        lambda p: Reducer("data").reduce(p),
        mesh=mesh, in_specs=P("data"), out_specs=P("data")))(params)
    np.testing.assert_allclose(np.asarray(out), np.full((8, 1), 3.5), rtol=1e-6)


# ---------------------------------------------------------------------------
# SyncBatchNorm
# ---------------------------------------------------------------------------

def test_syncbn_matches_full_batch_bn():
    """Distributed stats == single-device full-batch stats
    (two_gpu_unit_test.py parity model)."""
    mesh = _mesh()
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(16, 6, 5, 5), jnp.float32)  # NCHW
    bn = SyncBatchNorm(6, axis_name="data")
    params, state = bn.init()

    @jax.jit
    def dist(x):
        return shard_map(
            lambda x: bn(params, state, x, training=True)[0],
            mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)

    out_dist = dist(x)
    bn_local = SyncBatchNorm(6, axis_name=None)
    out_ref, new_state = bn_local(params, state, x, training=True)
    np.testing.assert_allclose(np.asarray(out_dist), np.asarray(out_ref),
                               rtol=1e-4, atol=1e-5)

    # running stats match torch convention
    import torch
    tbn = torch.nn.BatchNorm2d(6, momentum=0.1)
    tbn.train()
    tout = tbn(torch.tensor(np.asarray(x)))
    np.testing.assert_allclose(np.asarray(new_state.running_mean),
                               tbn.running_mean.numpy(), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(new_state.running_var),
                               tbn.running_var.numpy(), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_ref), tout.detach().numpy(),
                               rtol=1e-4, atol=1e-4)


def test_syncbn_backward_through_psum():
    """AD through the psum reproduces the reference's allreduced backward:
    grads must equal single-device full-batch BN grads."""
    mesh = _mesh()
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(16, 4), jnp.float32)
    dy = jnp.asarray(rng.randn(16, 4), jnp.float32)
    bn = SyncBatchNorm(4, axis_name="data", channel_axis=-1)
    params, state = bn.init()

    def dist_loss(params, x):
        def inner(params, x, dy):
            out, _ = bn(params, state, x, training=True)
            return jax.lax.psum(jnp.sum(out * dy), "data")
        return shard_map(inner, mesh=mesh, in_specs=(P(), P("data"), P("data")),
                         out_specs=P())(params, x, dy)

    def ref_loss(params, x):
        bn_local = SyncBatchNorm(4, axis_name=None, channel_axis=-1)
        out, _ = bn_local(params, state, x, training=True)
        return jnp.sum(out * dy)

    g_dist = jax.jit(jax.grad(dist_loss))(params, x)
    g_ref = jax.grad(ref_loss)(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(g_dist),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_syncbn_groups_uneven_semantics():
    """Process-group BN (test_groups.py): groups of 4 normalize separately."""
    mesh = _mesh()
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(8, 3), jnp.float32)
    groups = create_syncbn_process_group(4, 8)
    assert groups == [[0, 1, 2, 3], [4, 5, 6, 7]]
    bn = SyncBatchNorm(3, axis_name="data", axis_index_groups=groups,
                       channel_axis=-1)
    params, state = bn.init()

    @jax.jit
    def dist(x):
        return shard_map(lambda x: bn(params, state, x, training=True)[0],
                         mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)

    out = np.asarray(dist(x))
    # each group of 4 rows is normalized with its own stats
    bn_local = SyncBatchNorm(3, axis_name=None, channel_axis=-1)
    for lo, hi in [(0, 4), (4, 8)]:
        ref, _ = bn_local(params, state, x[lo:hi], training=True)
        np.testing.assert_allclose(out[lo:hi], np.asarray(ref),
                                   rtol=1e-4, atol=1e-5)


def test_syncbn_eval_and_fused_relu_and_z():
    bn = SyncBatchNorm(4, channel_axis=-1, fuse_relu=True)
    params, state = bn.init()
    x = jnp.asarray(np.random.RandomState(6).randn(10, 4), jnp.float32)
    z = jnp.ones((10, 4), jnp.float32) * 0.5
    out, _ = bn(params, state, x, training=True, z=z)
    assert (np.asarray(out) >= 0).all()  # relu applied
    out_eval, st = bn(params, state, x, training=False)
    assert int(st.num_batches_tracked) == 0  # eval does not update


def test_convert_syncbn_model():
    class Net:
        def __init__(self):
            self.bn1 = SyncBatchNorm(4)
            self.blocks = [SyncBatchNorm(8), "not-a-bn"]

    net = convert_syncbn_model(Net(), axis_name="data")
    assert net.bn1.axis_name == "data"
    assert net.blocks[0].axis_name == "data"
    assert net.blocks[1] == "not-a-bn"


def test_uneven_group_averaging():
    """Each rank averages by its OWN group size (review fix)."""
    mesh = _mesh()
    grads = jnp.ones((8, 2), jnp.float32)
    groups = [[0, 1], [2, 3, 4, 5, 6, 7]]

    @jax.jit
    def run(g):
        return shard_map(
            lambda g: allreduce_grads({"w": g}, "data",
                                      axis_index_groups=groups)["w"],
            mesh=mesh, in_specs=P("data"), out_specs=P("data"))(g)

    out = np.asarray(run(grads))
    np.testing.assert_allclose(out, np.ones((8, 2)), rtol=1e-6)


def test_syncbn_track_running_stats_false():
    bn = SyncBatchNorm(4, channel_axis=-1, track_running_stats=False)
    params, state = bn.init()
    x = jnp.asarray(np.random.RandomState(8).randn(10, 4) * 3 + 5, jnp.float32)
    out_eval, st = bn(params, state, x, training=False)
    # batch stats used even in eval: output is normalized
    assert abs(float(np.asarray(out_eval).mean())) < 1e-5
    # state untouched
    np.testing.assert_array_equal(np.asarray(st.running_mean),
                                  np.asarray(state.running_mean))
    assert int(st.num_batches_tracked) == 0


def test_syncbn_apply_dtype_matches_fp32_path():
    """apply_dtype folds the normalize to a per-channel x*a+b at input
    precision; statistics stay fp32, so outputs match the fp32 path to
    bf16 rounding and the running stats match exactly (docs/PERF.md)."""
    from apex_tpu.parallel.sync_batchnorm import sync_batch_norm

    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(64, 8) * 2 + 1, jnp.bfloat16)
    w = jnp.asarray(rng.rand(8) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(8), jnp.float32)
    z = jnp.asarray(rng.randn(64, 8), jnp.bfloat16)
    _, state = SyncBatchNorm(8, channel_axis=-1).init()

    ref, st_ref = sync_batch_norm(x, w, b, state, training=True,
                                  channel_axis=-1, z=z, fuse_relu=True)
    fast, st_fast = sync_batch_norm(x, w, b, state, training=True,
                                    channel_axis=-1, z=z, fuse_relu=True,
                                    apply_dtype=jnp.bfloat16)
    assert fast.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(fast, np.float32), np.asarray(ref, np.float32),
        rtol=0.05, atol=0.05)
    # statistics are identical — only the elementwise apply changed
    np.testing.assert_array_equal(np.asarray(st_fast.running_mean),
                                  np.asarray(st_ref.running_mean))
    np.testing.assert_array_equal(np.asarray(st_fast.running_var),
                                  np.asarray(st_ref.running_var))

    # gradients flow and stay finite through the folded path
    def loss(x):
        out, _ = sync_batch_norm(x, w, b, state, training=True,
                                 channel_axis=-1, apply_dtype=jnp.bfloat16)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g = jax.grad(loss)(x)
    assert g.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# delay_allreduce / gradient accumulation (apex no_sync semantics)
# ---------------------------------------------------------------------------

def test_delay_allreduce_returns_unsynced_grads():
    """DDP(delay_allreduce=True) is real: value_and_grad skips the inline
    sync (zero psums in its jaxpr) and returns per-replica grads."""
    mesh = _mesh()
    x = jnp.asarray(np.random.RandomState(3).randn(16, 4), jnp.float32)
    y = jnp.asarray(np.random.RandomState(4).randn(16, 1), jnp.float32)
    w = jnp.asarray(np.random.RandomState(5).randn(4, 1), jnp.float32)

    def loss_fn(w, x, y):
        return jnp.mean((x @ w - y) ** 2)

    def run(ddp, stacked):
        def wrapped(w, x, y):
            def inner(w, x, y):
                g = ddp.value_and_grad(loss_fn)(w, x, y)[1]
                # unsynced grads are per-rank: stack them on a sharded
                # leading axis to bring every replica's copy out
                return g[None] if stacked else g
            return shard_map(
                inner, mesh=mesh, in_specs=(P(), P("data"), P("data")),
                out_specs=P("data") if stacked else P())(w, x, y)
        return wrapped

    delayed = run(DistributedDataParallel(axis_name="data",
                                          delay_allreduce=True), True)
    synced = run(DistributedDataParallel(axis_name="data"), False)
    # the delayed jaxpr has no psum; the synced one has exactly one
    assert jaxpr_str(delayed, w, x, y).count("psum") == 0
    assert jaxpr_str(synced, w, x, y).count("psum") == 1
    # and its value is each replica's own-shard grad, not the mean
    g_delay = jax.jit(delayed)(w, x, y)  # (8, 4, 1): per-rank grads
    g_sync = jax.jit(synced)(w, x, y)
    assert g_delay.shape == (8, 4, 1)
    per_rank = np.stack([
        np.asarray(jax.grad(loss_fn)(w, x[i * 2:(i + 1) * 2],
                                     y[i * 2:(i + 1) * 2]))
        for i in range(8)])
    np.testing.assert_allclose(np.asarray(g_delay).reshape(8, 4, 1),
                               per_rank, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_sync), per_rank.mean(0),
                               rtol=1e-5, atol=1e-6)


def test_accumulate_gradients_single_psum():
    """The gradient-accumulation window fires exactly ONE allreduce: the
    jaxpr over K microbatches holds a single psum (vs K for per-microbatch
    sync), and the result equals the full-batch DDP grads."""
    from apex_tpu.training import accumulate_gradients

    mesh = _mesh()
    rng = np.random.RandomState(6)
    K = 3
    w = jnp.asarray(rng.randn(4, 2), jnp.float32)
    xs = jnp.asarray(rng.randn(K, 16, 4), jnp.float32)
    ys = jnp.asarray(rng.randn(K, 16, 2), jnp.float32)

    def loss_fn(w, mb):
        x, y = mb
        return jnp.mean((x @ w - y) ** 2)

    ddp = DistributedDataParallel(axis_name="data", delay_allreduce=True)

    def run(w, xs, ys):
        def inner(w, xs, ys):
            loss, grads = accumulate_gradients(ddp, loss_fn, w, (xs, ys))
            # the window loss is rank-local: bring the replicas out stacked
            return jnp.reshape(loss, (1,)), grads
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(), P(None, "data"), P(None, "data")),
                         out_specs=(P("data"), P()))(w, xs, ys)

    # exactly one psum per accumulation window (single-leaf params)
    assert jaxpr_str(run, w, xs, ys).count("psum") == 1

    _, g = jax.jit(run)(w, xs, ys)

    # reference: grad of the mean loss over all K x full-batch samples
    def ref_loss(w):
        return jnp.mean(jax.vmap(
            lambda x, y: jnp.mean((x @ w - y) ** 2))(xs, ys))

    gr = jax.grad(ref_loss)(w)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gr), rtol=1e-5,
                               atol=1e-6)


def test_accumulate_gradients_rejects_ragged_microbatches():
    from apex_tpu.training import accumulate_gradients

    ddp = DistributedDataParallel(axis_name="data", delay_allreduce=True)
    w = jnp.zeros((2, 2))
    with pytest.raises(ValueError, match="accumulation axis"):
        accumulate_gradients(ddp, lambda w, mb: jnp.sum(w), w,
                             (jnp.zeros((3, 2)), jnp.zeros((4, 2))))

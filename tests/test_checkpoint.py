"""Checkpoint/resume tests.

Model: the reference's bitwise-resume recipe (``reference:README.md:57-97``),
amp scaler persistence (``reference:apex/amp/frontend.py:361-400``), the
fp32-on-disk rule of ``O2StateDictHook``
(``reference:apex/amp/_initialize.py:133-142``), and sharded optimizer
state_dicts (``reference:apex/contrib/optimizers/distributed_fused_adam_v2.py``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.amp.scaler import DynamicLossScale, all_finite
from apex_tpu.checkpoint import (all_steps, latest_step, restore_checkpoint,
                                 save_checkpoint)
from apex_tpu.optimizers import (DistributedFusedAdam, FusedAdam, FusedSGD,
                                 ZeroAdamState)
from apex_tpu.transformer.pipeline_parallel.microbatches import (
    RampupBatchsizeNumMicroBatches)
from apex_tpu.transformer.tensor_parallel.random import RNGStatesTracker


def _bits(tree):
    out = []
    for x in jax.tree_util.tree_leaves(tree):
        if not hasattr(x, "dtype"):
            continue
        if jnp.issubdtype(x.dtype, jax.dtypes.prng_key):
            x = jax.random.key_data(x)
        out.append((str(np.asarray(x).dtype), np.asarray(x).tobytes()))
    return out


def test_roundtrip_bitwise_identity(tmp_path):
    """save → restore is the identity for every leaf, across dtypes and
    PRNG-key flavors."""
    state = {
        "w32": jnp.asarray(np.random.RandomState(0).randn(5, 3), jnp.float32),
        "wb16": jnp.asarray(
            np.random.RandomState(1).randn(7), jnp.bfloat16),
        "w16": jnp.asarray(np.random.RandomState(2).randn(4), jnp.float16),
        "step": jnp.asarray(11, jnp.int32),
        "legacy_key": jax.random.PRNGKey(42),
        "typed_key": jax.random.key(43),
    }
    save_checkpoint(str(tmp_path), state, step=11)
    restored, host = restore_checkpoint(str(tmp_path), state)
    assert _bits(restored) == _bits(state)
    # typed key stays typed
    assert jnp.issubdtype(restored["typed_key"].dtype, jax.dtypes.prng_key)


def test_fp32_on_disk_loadable_into_fp32_model(tmp_path):
    """The O2StateDictHook rule: a bf16-trained model's checkpoint restores
    directly into an fp32 (O0) model with full-precision values."""
    w = jnp.asarray([1.5, -2.25, 3.0], jnp.bfloat16)
    save_checkpoint(str(tmp_path), {"w": w}, step=0)
    target32 = {"w": jax.ShapeDtypeStruct((3,), jnp.float32)}
    restored, _ = restore_checkpoint(str(tmp_path), target32)
    assert restored["w"].dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(w, np.float32))


def test_latest_step_keep_and_host_state(tmp_path):
    calc = RampupBatchsizeNumMicroBatches(4, 4, 64, 16, 2, 1)
    calc.update(40, False)
    for s in (1, 3, 7):
        save_checkpoint(str(tmp_path), {"x": jnp.zeros(2)}, step=s,
                        host_state={"microbatch_calculator":
                                    calc.state_dict(),
                                    "consumed_samples": 40},
                        keep=2)
    assert latest_step(str(tmp_path)) == 7
    assert all_steps(str(tmp_path)) == [3, 7]
    _, host = restore_checkpoint(str(tmp_path), {"x": jnp.zeros(2)})
    calc2 = RampupBatchsizeNumMicroBatches(4, 4, 64, 16, 2, 1)
    calc2.load_state_dict(host["microbatch_calculator"])
    assert calc2.num_micro_batches == calc.num_micro_batches
    assert calc2.current_global_batch_size == calc.current_global_batch_size


def _train_setup(dtype):
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(8, 8), dtype),
              "b": jnp.asarray(rng.randn(8), dtype)}
    x = jnp.asarray(rng.randn(16, 8), dtype)
    y = jnp.asarray(rng.randn(16, 8), jnp.float32)
    opt = FusedAdam(lr=1e-2)
    scaler = DynamicLossScale(init_scale=2.0 ** 8, growth_interval=3)

    @jax.jit
    def step(params, opt_state, ls):
        def loss_fn(p):
            h = x @ p["w"] + p["b"]
            return jnp.mean((h.astype(jnp.float32) - y) ** 2) * ls.loss_scale
        grads = jax.grad(loss_fn)(params)
        grads = scaler.unscale(ls, grads)
        finite = all_finite(grads)
        new_ls = scaler.update(ls, finite)
        params, opt_state = opt.step(grads, opt_state, params,
                                     grads_finite=finite)
        return params, opt_state, new_ls

    return params, opt, scaler, step


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bitwise_resume(tmp_path, dtype):
    """5 steps + save + restore + 5 more == 10 straight steps, bitwise —
    params, optimizer moments, and loss-scaler scalars all resume exactly,
    including through the fp32-on-disk widening for bf16 params."""
    params, opt, scaler, step = _train_setup(dtype)
    state = {"params": params, "opt": opt.init(params), "ls": scaler.init()}

    ref = dict(state)
    for _ in range(10):
        ref["params"], ref["opt"], ref["ls"] = step(
            ref["params"], ref["opt"], ref["ls"])

    run = dict(state)
    for _ in range(5):
        run["params"], run["opt"], run["ls"] = step(
            run["params"], run["opt"], run["ls"])
    save_checkpoint(str(tmp_path), run, step=5)
    restored, _ = restore_checkpoint(str(tmp_path), run)
    for _ in range(5):
        restored["params"], restored["opt"], restored["ls"] = step(
            restored["params"], restored["opt"], restored["ls"])

    assert _bits(restored) == _bits(ref)


def test_resume_under_tp2(tmp_path):
    """TP-sharded params keep values and shardings through save/restore."""
    mesh = Mesh(np.array(jax.devices()[:2]), ("tensor",))
    sh = NamedSharding(mesh, P(None, "tensor"))
    w = jax.device_put(
        jnp.asarray(np.random.RandomState(3).randn(8, 4), jnp.float32), sh)
    save_checkpoint(str(tmp_path), {"w": w}, step=0)
    target = {"w": jax.ShapeDtypeStruct(w.shape, w.dtype, sharding=sh)}
    restored, _ = restore_checkpoint(str(tmp_path), target)
    assert restored["w"].sharding == sh
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))


def test_bitwise_resume_distributed_fused_adam(tmp_path):
    """ZeRO resume: the sharded master/moment flat shards round-trip with
    their P('data') sharding and continue bitwise."""
    DP = 4
    mesh = Mesh(np.array(jax.devices()[:DP]), ("data",))
    opt = DistributedFusedAdam(lr=1e-2)
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(6, 11), jnp.float32),
              "b": jnp.asarray(rng.randn(11), jnp.float32)}
    grads_stacked = jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(DP, *np.shape(p)), jnp.float32),
        params)
    state_spec = ZeroAdamState(step=P(), master=P("data"),
                               exp_avg=P("data"), exp_avg_sq=P("data"),
                               bucket_stamp=P())
    gspec = jax.tree_util.tree_map(lambda _: P("data"), grads_stacked)

    @jax.jit
    def init_fn(params):
        return shard_map(lambda p: opt.init(p), mesh=mesh,
                         in_specs=(P(),), out_specs=state_spec)(params)

    @jax.jit
    def step_fn(params, state, grads_stacked):
        def inner(params, state, g):
            g0 = jax.tree_util.tree_map(lambda s: s[0], g)
            return opt.step(g0, state, params)
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(), state_spec, gspec),
                         out_specs=(P(), state_spec))(
                             params, state, grads_stacked)

    ref_p, ref_s = params, init_fn(params)
    for _ in range(6):
        ref_p, ref_s = step_fn(ref_p, ref_s, grads_stacked)

    p, s = params, init_fn(params)
    for _ in range(3):
        p, s = step_fn(p, s, grads_stacked)
    save_checkpoint(str(tmp_path), {"params": p, "opt": s}, step=3)
    restored, _ = restore_checkpoint(str(tmp_path), {"params": p, "opt": s})
    # shardings preserved on the flat shards
    assert restored["opt"].master.sharding.spec == P("data")
    p, s = restored["params"], restored["opt"]
    for _ in range(3):
        p, s = step_fn(p, s, grads_stacked)

    assert _bits((p, s)) == _bits((ref_p, ref_s))


def test_rng_tracker_states_roundtrip(tmp_path):
    tracker = RNGStatesTracker()
    tracker.add("model-parallel-rng", 123)
    tracker.add("data-parallel-rng", 7)
    tracker.make_key("model-parallel-rng")  # advance
    save_checkpoint(str(tmp_path), {"rng": tracker.get_states()}, step=0)
    restored, _ = restore_checkpoint(str(tmp_path),
                                     {"rng": tracker.get_states()})
    t2 = RNGStatesTracker()
    t2.set_states(restored["rng"])
    k1 = tracker.make_key("model-parallel-rng")
    k2 = t2.make_key("model-parallel-rng")
    np.testing.assert_array_equal(np.asarray(k1), np.asarray(k2))


def test_restore_missing_and_uncommitted(tmp_path):
    with pytest.raises(FileNotFoundError):
        restore_checkpoint(str(tmp_path), {"x": jnp.zeros(1)})
    # a checkpoint without its COMMITTED marker is invisible
    path = save_checkpoint(str(tmp_path), {"x": jnp.zeros(1)}, step=4)
    import os
    os.remove(os.path.join(path, "COMMITTED"))
    assert latest_step(str(tmp_path)) is None


def test_restore_skips_torn_dir_with_warning(tmp_path):
    """A torn dir NEWER than the latest COMMITTED step (a writer died
    mid-save) is skipped loudly: latest-step restore warns naming the
    skipped step and falls back to the committed one."""
    import os

    from apex_tpu.checkpoint import torn_steps

    save_checkpoint(str(tmp_path), {"x": jnp.full(2, 1.0)}, step=1)
    path2 = save_checkpoint(str(tmp_path), {"x": jnp.full(2, 2.0)}, step=2)
    os.remove(os.path.join(path2, "COMMITTED"))
    assert torn_steps(str(tmp_path)) == [2]
    with pytest.warns(UserWarning, match=r"torn.*\[2\]"):
        restored, host = restore_checkpoint(str(tmp_path),
                                            {"x": jnp.zeros(2)})
    np.testing.assert_array_equal(np.asarray(restored["x"]), [1.0, 1.0])


def test_restore_with_only_torn_dirs_names_them(tmp_path):
    import os

    path = save_checkpoint(str(tmp_path), {"x": jnp.zeros(1)}, step=3)
    os.remove(os.path.join(path, "COMMITTED"))
    with pytest.warns(UserWarning, match="torn"):
        with pytest.raises(FileNotFoundError, match=r"torn.*\[3\]"):
            restore_checkpoint(str(tmp_path), {"x": jnp.zeros(1)})


def test_keep_last_is_canonical_keep_spelling(tmp_path):
    for s in (1, 2, 3):
        save_checkpoint(str(tmp_path), {"x": jnp.zeros(2)}, step=s,
                        keep_last=2)
    assert all_steps(str(tmp_path)) == [2, 3]
    # conflicting double spelling is rejected
    with pytest.raises(ValueError, match="keep_last"):
        save_checkpoint(str(tmp_path), {"x": jnp.zeros(2)}, step=4,
                        keep=1, keep_last=2)
    with pytest.raises(ValueError):
        save_checkpoint(str(tmp_path), {"x": jnp.zeros(2)}, step=4,
                        keep_last=0)

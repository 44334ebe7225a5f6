"""Pipeline-schedule backward memory accounting.

The reference's 1F1B exists to bound in-flight activations at O(pp)
microbatches (``reference:apex/transformer/pipeline_parallel/schedules/
fwd_bwd_pipelining_without_interleaving.py:155-345``,
``free_output_tensor`` at ``common.py:198-249``). The default
``memory_efficient=True`` schedule reproduces that bound with a
hand-driven vjp inside the tick scan — asserted here as O(1)-in-M
compiled temp memory. The AD-through-the-scan driver
(``memory_efficient=False``) keeps its documented O(M + L) per-tick
residual profile, with ``remat=True`` collapsing each tick's residual to
the carry; both profiles are pinned with XLA's compiled memory analysis
on the CPU backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.pipeline_parallel import (
    forward_backward_pipelining_without_interleaving)

PP = 4
D = 128
MB = 4
LAYERS_PER_STAGE = 3


@pytest.fixture
def mesh():
    m = parallel_state.initialize_model_parallel(
        pipeline_model_parallel_size=PP)
    yield m
    parallel_state.destroy_model_parallel()


def _stage_fn(p, x, s):
    # 3 "layers" per stage so intra-stage residuals dominate the carry
    for _ in range(LAYERS_PER_STAGE):
        x = jnp.tanh(x @ p["w"])
    return x


def _temp_bytes(mesh, M, remat, memory_efficient):
    rng = np.random.RandomState(0)
    ws = jnp.asarray(rng.randn(PP, D, D) * 0.1, jnp.float32)
    micro = jnp.asarray(rng.randn(M, MB, D), jnp.float32)

    def run(ws):
        def inner(ws):
            loss, grads = forward_backward_pipelining_without_interleaving(
                _stage_fn, micro, {"w": ws[0]},
                loss_fn=lambda y, m: jnp.mean(y ** 2), remat=remat,
                memory_efficient=memory_efficient)
            return loss, grads
        return shard_map(inner, mesh=mesh, in_specs=(P("pipe"),),
                         out_specs=(P(), {"w": P("pipe")}))(ws)

    compiled = jax.jit(run).lower(ws).compile()
    return compiled.memory_analysis().temp_size_in_bytes


def test_memory_efficient_1f1b_is_O1_in_microbatches(mesh):
    """The default schedule holds O(pp) activations regardless of M — the
    reference 1F1B's whole point. Temp memory must be flat in M (scan
    bookkeeping only; far below one activation per extra microbatch)."""
    t8 = _temp_bytes(mesh, 8, remat=False, memory_efficient=True)
    t32 = _temp_bytes(mesh, 32, remat=False, memory_efficient=True)
    act_bytes = MB * D * 4
    slope = (t32 - t8) / 24
    assert slope < act_bytes / 4, (t8, t32)


def test_memory_efficient_matches_ad_schedule_outputs(mesh):
    """Same loss and grads as the AD-through-the-scan driver (which is
    itself pinned against no-pipelining elsewhere)."""
    rng = np.random.RandomState(1)
    ws = jnp.asarray(rng.randn(PP, D, D) * 0.1, jnp.float32)
    micro = jnp.asarray(rng.randn(8, MB, D), jnp.float32)

    def run(memory_efficient):
        def inner(ws):
            return forward_backward_pipelining_without_interleaving(
                _stage_fn, micro, {"w": ws[0]},
                loss_fn=lambda y, m: jnp.mean(y ** 2),
                memory_efficient=memory_efficient)
        return shard_map(inner, mesh=mesh, in_specs=(P("pipe"),),
                         out_specs=(P(), {"w": P("pipe")}))(ws)

    loss_a, grads_a = run(True)
    loss_b, grads_b = run(False)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grads_a["w"]),
                               np.asarray(grads_b["w"]),
                               rtol=1e-5, atol=1e-6)


def test_ad_schedule_backward_memory_is_linear_in_microbatches(mesh):
    """Honest bound for the AD driver: residual memory grows ~linearly with
    M (ticks), unlike the default's O(pp)."""
    t8 = _temp_bytes(mesh, 8, remat=False, memory_efficient=False)
    t32 = _temp_bytes(mesh, 32, remat=False, memory_efficient=False)
    slope = (t32 - t8) / 24
    assert slope > 0
    # per-tick residual must be at least the carry (one activation/chunk)
    carry_bytes = MB * D * 4
    assert slope >= carry_bytes


def test_ad_schedule_remat_bounds_residuals_to_the_carry(mesh):
    """With remat=True each tick's residual is the carry (plus bounded
    bookkeeping), not the per-layer intermediates."""
    slope_plain = (_temp_bytes(mesh, 32, False, False)
                   - _temp_bytes(mesh, 8, False, False)) / 24
    slope_remat = (_temp_bytes(mesh, 32, True, False)
                   - _temp_bytes(mesh, 8, True, False)) / 24
    carry_bytes = MB * D * 4
    # intra-stage residuals (3 tanh layers) are recomputed, not stored
    assert slope_remat <= slope_plain / 2
    assert slope_remat <= 4 * carry_bytes


def test_memory_efficient_matches_ad_schedule_shared_params(mesh):
    """The shared-params/embed_fn path (pipelined embedding + tied-head
    grads, psum-reconciled across stages) must match the AD driver
    value-for-value — loss, stage grads, AND shared grads."""
    rng = np.random.RandomState(4)
    ws = jnp.asarray(rng.randn(PP, D, D) * 0.1, jnp.float32)
    emb = jnp.asarray(rng.randn(16, D) * 0.1, jnp.float32)
    micro = jnp.asarray(rng.randint(0, 16, (8, MB)), jnp.int32)

    def embed_fn(shared, mb):
        return jnp.take(shared["e"], mb, axis=0)

    def loss_fn(shared, y, m):
        # tied head: project back onto the embedding
        return jnp.mean((y @ shared["e"].T) ** 2)

    def run(memory_efficient):
        def inner(ws, shared):
            return forward_backward_pipelining_without_interleaving(
                _stage_fn, micro, {"w": ws[0]},
                loss_fn=loss_fn, shared_params=shared, embed_fn=embed_fn,
                memory_efficient=memory_efficient)
        return shard_map(inner, mesh=mesh,
                         in_specs=(P("pipe"), {"e": P()}),
                         out_specs=(P(), ({"w": P("pipe")}, {"e": P()})))(
                             ws, {"e": emb})

    loss_a, (sg_a, shg_a) = run(True)
    loss_b, (sg_b, shg_b) = run(False)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sg_a["w"]), np.asarray(sg_b["w"]),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(shg_a["e"]),
                               np.asarray(shg_b["e"]),
                               rtol=1e-5, atol=1e-7)


def test_memory_efficient_interleaved_is_O1_in_microbatches(mesh):
    """The interleaved (vpp) driver holds O(L = pp*vpp) activations
    regardless of M, like the single-chunk case."""
    from apex_tpu.transformer.pipeline_parallel import (
        forward_backward_pipelining_with_interleaving)

    VPP = 2
    rng = np.random.RandomState(0)
    ws = jnp.asarray(rng.randn(PP, VPP, D, D) * 0.1, jnp.float32)

    def temp_bytes(M):
        micro = jnp.asarray(rng.randn(M, MB, D), jnp.float32)

        def run(ws):
            def inner(ws):
                return forward_backward_pipelining_with_interleaving(
                    _stage_fn, micro, {"w": ws[0]},
                    loss_fn=lambda y, m: jnp.mean(y ** 2),
                    num_model_chunks=VPP)
            return shard_map(inner, mesh=mesh, in_specs=(P("pipe"),),
                             out_specs=(P(), {"w": P("pipe")}))(ws)

        compiled = jax.jit(run).lower(ws).compile()
        return compiled.memory_analysis().temp_size_in_bytes

    t8, t32 = temp_bytes(8), temp_bytes(32)
    act_bytes = MB * D * 4
    slope = (t32 - t8) / 24
    assert slope < act_bytes / 4, (t8, t32)


def test_interleaved_num_model_chunks_one(mesh):
    """Regression: the interleaved API with num_model_chunks=1 (params
    carrying the documented leading (1, ...) chunk axis) must work under
    the memory-efficient default and match the AD driver."""
    from apex_tpu.transformer.pipeline_parallel import (
        forward_backward_pipelining_with_interleaving)

    rng = np.random.RandomState(5)
    ws = jnp.asarray(rng.randn(PP, 1, D, D) * 0.1, jnp.float32)
    micro = jnp.asarray(rng.randn(8, MB, D), jnp.float32)

    def run(memory_efficient):
        def inner(ws):
            return forward_backward_pipelining_with_interleaving(
                _stage_fn, micro, {"w": ws[0]},
                loss_fn=lambda y, m: jnp.mean(y ** 2),
                num_model_chunks=1, memory_efficient=memory_efficient)
        return shard_map(inner, mesh=mesh, in_specs=(P("pipe"),),
                         out_specs=(P(), {"w": P("pipe")}))(ws)

    loss_a, grads_a = run(True)
    loss_b, grads_b = run(False)
    np.testing.assert_allclose(float(loss_a), float(loss_b), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(grads_a["w"]),
                               np.asarray(grads_b["w"]),
                               rtol=1e-5, atol=1e-6)

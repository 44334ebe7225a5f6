"""Ring attention + sequence-parallel mapping tests (long-context layer;
beyond-reference capability — the reference has no CP/SP at all)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.ops.flash_attention import mha_reference
from apex_tpu.transformer.context_parallel import (
    gather_from_sequence_parallel_region, reduce_scatter_to_sequence_parallel_region,
    ring_attention, scatter_to_sequence_parallel_region)

CP = 4


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:CP]), ("context",))


def _qkv(b=2, h=2, s=64, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(mesh, causal):
    q, k, v = _qkv(seed=1)

    def run(q, k, v):
        def inner(q, k, v):
            return ring_attention(q, k, v, "context", causal=causal)
        spec = P(None, None, "context", None)
        return shard_map(inner, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec)(q, k, v)

    out = jax.jit(run)(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("remat", [True, False])
def test_ring_attention_grads_match_reference(mesh, remat):
    q, k, v = _qkv(seed=2)
    dy = jnp.asarray(np.random.RandomState(3).randn(*q.shape), jnp.float32)

    def ring_loss(q, k, v):
        def inner(q, k, v):
            out = ring_attention(q, k, v, "context", causal=True,
                                 remat=remat)
            return jax.lax.psum(jnp.sum(out * _shard(dy)), "context")

        def _shard(x):
            from jax.lax import axis_size
            cp = axis_size("context")
            r = jax.lax.axis_index("context")
            chunk = x.shape[2] // cp
            return jax.lax.dynamic_slice_in_dim(x, r * chunk, chunk, 2)

        spec = P(None, None, "context", None)
        return shard_map(inner, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=P())(q, k, v)

    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=True) * dy),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_ring_attention_bf16_and_uneven_rank_content(mesh):
    """bf16 inputs, fp32 accumulation; content differs per rank so any
    rotation-order bug shows up."""
    q, k, v = _qkv(b=1, h=1, s=128, d=8, seed=4)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def run(q, k, v):
        spec = P(None, None, "context", None)
        return shard_map(
            lambda q, k, v: ring_attention(q, k, v, "context", causal=True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)(q, k, v)

    out = jax.jit(run)(q, k, v)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=3e-2, atol=3e-2)


def test_sequence_parallel_mappings_roundtrip(mesh):
    """scatter -> gather is the identity; reduce_scatter + gather == psum
    (the Megatron-LM SP identities), with ``context`` standing in for the
    tensor axis."""
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(16, 3, 8), jnp.float32)

    def roundtrip(x):
        def inner(x):
            s = scatter_to_sequence_parallel_region(x, "context")
            g = gather_from_sequence_parallel_region(s, "context")
            return jax.lax.pmean(g, "context")
        return shard_map(inner, mesh=mesh, in_specs=P(), out_specs=P())(x)

    np.testing.assert_allclose(np.asarray(jax.jit(roundtrip)(x)),
                               np.asarray(x), rtol=1e-6)

    def rs_then_gather(x):
        def inner(x):
            part = reduce_scatter_to_sequence_parallel_region(x, "context")
            return gather_from_sequence_parallel_region(part, "context")
        return shard_map(inner, mesh=mesh, in_specs=P("context"),
                         out_specs=P("context"))(x)

    # feeding per-rank copies xi: reduce_scatter sums them; gather
    # reassembles the summed sequence
    stacked = jnp.asarray(rng.randn(CP, 16, 3, 8), jnp.float32)
    out = jax.jit(rs_then_gather)(stacked.reshape(CP * 16, 3, 8))
    expect = np.sum(np.asarray(stacked), axis=0)
    np.testing.assert_allclose(
        np.asarray(out).reshape(CP, 16, 3, 8)[0], expect, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_reference(mesh, causal):
    from apex_tpu.transformer.context_parallel import ulysses_attention

    q, k, v = _qkv(b=2, h=4, s=64, d=16, seed=6)

    def run(q, k, v):
        def inner(q, k, v):
            return ulysses_attention(q, k, v, "context", causal=causal)
        spec = P(None, None, "context", None)
        return shard_map(inner, mesh=mesh, in_specs=(spec,) * 3,
                         out_specs=spec)(q, k, v)

    out = jax.jit(run)(q, k, v)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_attention_grads_and_validation(mesh):
    from apex_tpu.transformer.context_parallel import ulysses_attention

    q, k, v = _qkv(b=1, h=4, s=64, d=8, seed=7)
    dy_full = jnp.asarray(np.random.RandomState(8).randn(*q.shape),
                          jnp.float32)

    def loss(q, k, v):
        def inner(q, k, v, dy):
            out = ulysses_attention(q, k, v, "context", causal=True)
            return jax.lax.psum(jnp.sum(out * dy), "context")
        spec = P(None, None, "context", None)
        return shard_map(inner, mesh=mesh, in_specs=(spec,) * 4,
                         out_specs=P())(q, k, v, dy_full)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(mha_reference(q, k, v, causal=True)
                                * dy_full), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    # heads must divide cp
    q3, k3, v3 = _qkv(b=1, h=3, s=64, d=8, seed=9)
    with pytest.raises(ValueError):
        spec = P(None, None, "context", None)
        shard_map(
            lambda q, k, v: ulysses_attention(q, k, v, "context"),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)(q3, k3, v3)


def test_context_axis_in_parallel_state():
    """context_parallel_size carves a first-class mesh axis; ring attention
    runs over it inside the hybrid mesh, and the flat-rank group
    enumerations account for the new dimension."""
    from apex_tpu.transformer import parallel_state

    m = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2, context_parallel_size=2)
    try:
        assert parallel_state.get_context_parallel_world_size() == 2
        assert parallel_state.get_data_parallel_world_size() == 2
        # layout: tp fastest, then cp, then dp
        assert parallel_state.get_tensor_model_parallel_groups()[:2] == [
            [0, 1], [2, 3]]
        assert parallel_state.get_context_parallel_groups()[:2] == [
            [0, 2], [1, 3]]
        assert parallel_state.get_data_parallel_groups()[0] == [0, 4]

        q, k, v = _qkv(b=1, h=2, s=32, d=8, seed=10)

        def run(q, k, v):
            def inner(q, k, v):
                out = ring_attention(q, k, v, "context", causal=True)
                return jax.lax.pmean(jax.lax.pmean(
                    jax.lax.pmean(out, "data"), "tensor"), "pipe")
            spec = P(None, None, "context", None)
            return shard_map(inner, mesh=m, in_specs=(spec,) * 3,
                             out_specs=spec)(q, k, v)

        out = jax.jit(run)(q, k, v)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    finally:
        parallel_state.destroy_model_parallel()

"""Direct coverage for tensor_parallel/mappings.py forward/transpose pairs.

The four Megatron mapping pairs (copy/reduce/scatter/gather) were only
exercised indirectly through the GPT model; these tests pin each forward
collective and its AD transpose on a 2-device tensor mesh, plus the
divisibility guards (a floor-divide used to silently drop elements).
Models ``reference:tests/L0/run_transformer/test_mapping.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer import tensor_parallel as tp
from apex_tpu.transformer.context_parallel import (
    gather_from_sequence_parallel_region,
    reduce_scatter_to_sequence_parallel_region,
    scatter_to_sequence_parallel_region)
from jax import shard_map


@pytest.fixture
def mesh_tp2():
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2)
    yield mesh
    parallel_state.destroy_model_parallel()


def _smap(mesh, fn, in_specs, out_specs):
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs))


# ---------------------------------------------------------------------------
# copy: identity forward / allreduce backward
# ---------------------------------------------------------------------------

def test_copy_forward_identity_backward_psum(mesh_tp2):
    x = jnp.asarray(np.random.RandomState(0).randn(3, 4), jnp.float32)

    fwd = _smap(mesh_tp2,
                lambda x: jax.lax.pmean(
                    tp.copy_to_tensor_model_parallel_region(x), "tensor"),
                (P(),), P())
    np.testing.assert_array_equal(np.asarray(fwd(x)), np.asarray(x))

    # each rank consumes the copy independently; the transpose allreduces,
    # so d(sum over ranks of sum(x*r_weight)) = tp * x-grad-per-rank
    def loss(x):
        def inner(x):
            y = tp.copy_to_tensor_model_parallel_region(x)
            return jax.lax.psum(jnp.sum(y ** 2), "tensor") / 2.0
        return shard_map(inner, mesh=mesh_tp2, in_specs=(P(),),
                         out_specs=P())(x)

    g = jax.jit(jax.grad(loss))(x)
    np.testing.assert_allclose(np.asarray(g), 2.0 * np.asarray(x),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# reduce: allreduce forward / identity backward
# ---------------------------------------------------------------------------

def test_reduce_forward_sum_backward_identity(mesh_tp2):
    x = jnp.asarray(np.random.RandomState(1).randn(4, 6), jnp.float32)

    # x sharded over the last dim: each rank holds a distinct half; the
    # reduce sums rank-local squares into a replicated total
    def fwd(x):
        def inner(x):
            return tp.reduce_from_tensor_model_parallel_region(
                jnp.sum(x ** 2))
        return shard_map(inner, mesh=mesh_tp2, in_specs=(P(None, "tensor"),),
                         out_specs=P())(x)

    total = jax.jit(fwd)(x)
    np.testing.assert_allclose(float(total), float(jnp.sum(x ** 2)),
                               rtol=1e-6)
    # transpose of psum = identity-as-varying: plain d/dx of the total
    g = jax.jit(jax.grad(lambda x: fwd(x)))(x)
    np.testing.assert_allclose(np.asarray(g), 2.0 * np.asarray(x),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# scatter/gather: round trips both ways + transposes
# ---------------------------------------------------------------------------

def test_scatter_gather_roundtrip(mesh_tp2):
    x = jnp.asarray(np.random.RandomState(2).randn(4, 8), jnp.float32)

    def roundtrip(x):
        def inner(x):
            s = tp.scatter_to_tensor_model_parallel_region(x)
            g = tp.gather_from_tensor_model_parallel_region(s)
            return jax.lax.pmean(g, "tensor")
        return shard_map(inner, mesh=mesh_tp2, in_specs=(P(),),
                         out_specs=P())(x)

    np.testing.assert_allclose(np.asarray(jax.jit(roundtrip)(x)),
                               np.asarray(x), rtol=1e-6)

    # gather-then-scatter on sharded input is also identity (rank keeps
    # its own slice of the gathered value)
    def gs(x):
        def inner(x):
            g = tp.gather_from_tensor_model_parallel_region(x)
            return tp.scatter_to_tensor_model_parallel_region(g)
        return shard_map(inner, mesh=mesh_tp2,
                         in_specs=(P(None, "tensor"),),
                         out_specs=P(None, "tensor"))(x)

    np.testing.assert_allclose(np.asarray(jax.jit(gs)(x)), np.asarray(x),
                               rtol=1e-6)

    # scatter transpose: every element of x is consumed by exactly one
    # rank, so d(sum over ranks of sum(shard^2)) = 2x everywhere
    def loss(x):
        def inner(x):
            s = tp.scatter_to_tensor_model_parallel_region(x)
            return jax.lax.psum(jnp.sum(s ** 2), "tensor")
        return shard_map(inner, mesh=mesh_tp2, in_specs=(P(),),
                         out_specs=P())(x)

    g = jax.jit(jax.grad(loss))(x)
    np.testing.assert_allclose(np.asarray(g), 2.0 * np.asarray(x),
                               rtol=1e-6)

    # gather transpose: the gathered value feeds a replicated-weighted sum
    # on every rank; the reduce-scatter transpose hands each shard the sum
    # of its cotangents over ranks (= tp * its slice weight here)
    def loss_g(x):
        def inner(x):
            g = tp.gather_from_tensor_model_parallel_region(x)
            return jax.lax.psum(jnp.sum(g ** 2), "tensor") / 2.0
        return shard_map(inner, mesh=mesh_tp2,
                         in_specs=(P(None, "tensor"),),
                         out_specs=P())(x)

    g2 = jax.jit(jax.grad(loss_g))(x)
    np.testing.assert_allclose(np.asarray(g2), 2.0 * np.asarray(x),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# sequence-parallel mappings (context_parallel.py)
# ---------------------------------------------------------------------------

def test_sp_scatter_gather_roundtrip_and_reduce_scatter(mesh_tp2):
    x = jnp.asarray(np.random.RandomState(3).randn(2, 8, 4), jnp.float32)

    def roundtrip(x):
        def inner(x):
            s = scatter_to_sequence_parallel_region(x, "tensor", seq_axis=1)
            return gather_from_sequence_parallel_region(
                s, "tensor", seq_axis=1, invariant=True)
        return shard_map(inner, mesh=mesh_tp2, in_specs=(P(),),
                         out_specs=P())(x)

    np.testing.assert_allclose(np.asarray(jax.jit(roundtrip)(x)),
                               np.asarray(x), rtol=1e-6)

    # psum_scatter: each rank contributes the full sequence; shard r of the
    # output is the rank-sum of shard r of the contributions
    def rs(x):
        def inner(x):
            from apex_tpu.utils.vma import cast_to_vma
            contrib = cast_to_vma(x, frozenset({"tensor"}))
            return reduce_scatter_to_sequence_parallel_region(
                contrib, "tensor", seq_axis=1)
        return shard_map(inner, mesh=mesh_tp2, in_specs=(P(),),
                         out_specs=P(None, "tensor", None))(x)

    out = jax.jit(rs)(x)
    np.testing.assert_allclose(np.asarray(out), 2.0 * np.asarray(x),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# divisibility guards (the silent-truncation fix)
# ---------------------------------------------------------------------------

def test_scatter_rejects_indivisible_last_dim(mesh_tp2):
    x = jnp.ones((4, 7))  # 7 % 2 != 0: used to silently drop an element

    def run(x):
        return shard_map(
            lambda x: tp.scatter_to_tensor_model_parallel_region(x),
            mesh=mesh_tp2, in_specs=(P(),),
            out_specs=P(None, "tensor"))(x)

    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(run)(x)


def test_sp_scatter_rejects_indivisible_seq(mesh_tp2):
    x = jnp.ones((2, 7, 4))

    def run(x):
        return shard_map(
            lambda x: scatter_to_sequence_parallel_region(
                x, "tensor", seq_axis=1),
            mesh=mesh_tp2, in_specs=(P(),),
            out_specs=P(None, "tensor", None))(x)

    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(run)(x)

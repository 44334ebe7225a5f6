"""Spatial-parallel conv tests (``reference:apex/contrib/bottleneck``
SpatialBottleneck halo-exchange role)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel.spatial import halo_exchange, spatial_conv2d

SP = 4


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:SP]), ("spatial",))


def test_halo_exchange_rows(mesh):
    x = jnp.arange(SP * 2 * 3, dtype=jnp.float32).reshape(1, SP * 2, 3, 1)

    def run(x):
        return shard_map(
            lambda x: halo_exchange(x, "spatial", 1),
            mesh=mesh, in_specs=P(None, "spatial"),
            out_specs=P(None, "spatial"))(x)

    out = np.asarray(jax.jit(run)(x))  # (1, SP*(2+2), 3, 1)
    per = out.reshape(SP, 4, 3)
    full = np.asarray(x).reshape(SP * 2, 3)
    for r in range(SP):
        np.testing.assert_array_equal(per[r, 1:3], full[2 * r:2 * r + 2])
        if r > 0:
            np.testing.assert_array_equal(per[r, 0], full[2 * r - 1])
        else:
            assert np.all(per[r, 0] == 0)
        if r < SP - 1:
            np.testing.assert_array_equal(per[r, 3], full[2 * r + 2])
        else:
            assert np.all(per[r, 3] == 0)


@pytest.mark.parametrize("stride", [1, 2])
def test_spatial_conv_matches_dense(mesh, stride):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, SP * 4, 10, 3), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 3, 5) * 0.2, jnp.float32)

    def run(x, w):
        return shard_map(
            lambda x, w: spatial_conv2d(x, w, "spatial", stride=stride),
            mesh=mesh, in_specs=(P(None, "spatial"), P()),
            out_specs=P(None, "spatial"))(x, w)

    out = np.asarray(jax.jit(run)(x, w))
    ref = np.asarray(jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC")))
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_spatial_conv_grads_cross_shards(mesh):
    """Halo gradients must flow back to the neighboring shard's owner."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(1, SP * 2, 6, 2), jnp.float32)
    w = jnp.asarray(rng.randn(3, 3, 2, 2) * 0.2, jnp.float32)

    def loss(x, w):
        def inner(x, w):
            out = spatial_conv2d(x, w, "spatial")
            return jax.lax.psum(jnp.sum(out ** 2), "spatial")
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, "spatial"), P()),
                         out_specs=P())(x, w)

    gx, gw = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, w)

    def dense_loss(x, w):
        out = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jnp.sum(out ** 2)

    gx_ref, gw_ref = jax.grad(dense_loss, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(gw_ref),
                               rtol=2e-5, atol=2e-5)

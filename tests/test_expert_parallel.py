"""Expert-parallel MoE tests (beyond-reference: SURVEY §2.3 lists EP as
roadmap; the reference has none)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.transformer.expert_parallel import ExpertParallelMLP

EP = 4


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:EP]), ("expert",))


def _dense_reference(layer, params, x_shards):
    """Per-shard top-1 routing applied densely (no capacity drops)."""
    Wg = np.asarray(params["router"]["weight"], np.float64)
    outs = []
    for xs in x_shards:
        xs64 = np.asarray(xs, np.float64)
        gates = jax.nn.softmax(jnp.asarray(xs64 @ Wg.T), axis=-1)
        gates = np.asarray(gates)
        expert = gates.argmax(-1)
        out = np.zeros_like(xs64)
        for i, e in enumerate(expert):
            wi = np.asarray(params["experts"]["wi"][e], np.float64)
            bi = np.asarray(params["experts"]["bi"][e], np.float64)
            wo = np.asarray(params["experts"]["wo"][e], np.float64)
            bo = np.asarray(params["experts"]["bo"][e], np.float64)
            h1 = np.asarray(jax.nn.gelu(jnp.asarray(xs64[i] @ wi.T + bi),
                                        approximate=True))
            out[i] = gates[i, e] * (h1 @ wo.T + bo)
        outs.append(out)
    return np.concatenate(outs)


def test_moe_matches_dense_reference(mesh):
    rng = np.random.RandomState(0)
    layer = ExpertParallelMLP(16, 32, num_experts=8, capacity_factor=8.0,
                              axis_name="expert")
    params = layer.init(jax.random.PRNGKey(0))
    x = jnp.asarray(rng.randn(EP * 12, 16), jnp.float32)

    def run(params, x):
        def inner(params, x):
            out, aux = layer(params, x)
            return out, jax.lax.pmean(aux, "expert")
        espec = {"router": {"weight": P()},
                 "experts": jax.tree_util.tree_map(lambda _: P("expert"),
                                                   params["experts"])}
        return shard_map(inner, mesh=mesh, in_specs=(espec, P("expert")),
                         out_specs=(P("expert"), P()))(params, x)

    out, aux = jax.jit(run)(params, x)
    ref = _dense_reference(layer, params,
                           np.split(np.asarray(x), EP))
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-4, atol=2e-5)
    assert float(aux) >= 1.0 - 1e-5  # Switch aux lower bound at balance


def test_moe_capacity_drops_tokens(mesh):
    rng = np.random.RandomState(1)
    layer = ExpertParallelMLP(8, 16, num_experts=4, capacity_factor=0.25,
                              axis_name="expert")
    params = layer.init(jax.random.PRNGKey(1))
    x = jnp.asarray(rng.randn(EP * 16, 8), jnp.float32)

    def run(params, x):
        espec = {"router": {"weight": P()},
                 "experts": jax.tree_util.tree_map(lambda _: P("expert"),
                                                   params["experts"])}
        return shard_map(lambda p, x: layer(p, x)[0], mesh=mesh,
                         in_specs=(espec, P("expert")),
                         out_specs=P("expert"))(params, x)

    out = np.asarray(jax.jit(run)(params, x))
    zero_rows = np.all(out == 0.0, axis=-1).mean()
    assert zero_rows > 0.2  # capacity 1/token-per-expert drops plenty


def test_moe_grads_flow_to_router_and_experts(mesh):
    rng = np.random.RandomState(2)
    layer = ExpertParallelMLP(8, 16, num_experts=4, capacity_factor=4.0,
                              axis_name="expert")
    params = layer.init(jax.random.PRNGKey(2))
    x = jnp.asarray(rng.randn(EP * 8, 8), jnp.float32)

    def loss(params, x):
        espec = {"router": {"weight": P()},
                 "experts": jax.tree_util.tree_map(lambda _: P("expert"),
                                                   params["experts"])}

        def inner(params, x):
            out, aux = layer(params, x)
            return (jax.lax.psum(jnp.sum(out ** 2), "expert")
                    + 0.01 * jax.lax.pmean(aux, "expert"))
        return shard_map(inner, mesh=mesh, in_specs=(espec, P("expert")),
                         out_specs=P())(params, x)

    g = jax.jit(jax.grad(loss))(params, x)
    assert float(np.abs(np.asarray(g["router"]["weight"])).max()) > 0
    assert float(np.abs(np.asarray(g["experts"]["wi"])).max()) > 0

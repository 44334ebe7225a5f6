"""Minimal data-parallel training — ``reference:examples/simple/
distributed/distributed_data_parallel.py`` rebuilt on apex_tpu.

The reference spawns one process per GPU and wraps the model in apex DDP;
on TPU one process drives all devices and "DDP" is the
``DistributedDataParallel.value_and_grad`` wrapper inside ``shard_map``.

    python examples/simple_distributed.py
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.optimizers import FusedAdam
from apex_tpu.parallel import DistributedDataParallel


def main(steps: int = 20):
    mesh = Mesh(np.array(jax.devices()), ("data",))
    n_dev = jax.device_count()
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(32, 16) * 0.1, jnp.float32),
              "b": jnp.zeros(16, jnp.float32)}
    x = jnp.asarray(rng.randn(8 * n_dev, 32), jnp.float32)
    y = jnp.asarray(rng.randn(8 * n_dev, 16), jnp.float32)

    ddp = DistributedDataParallel(axis_name="data")
    opt = FusedAdam(lr=1e-2)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        def inner(params, opt_state, x, y):
            def loss_fn(p, x, y):
                return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)
            loss, grads = ddp.value_and_grad(loss_fn)(params, x, y)
            params, opt_state = opt.step(grads, opt_state, params)
            return params, opt_state, jax.lax.pmean(loss, "data")
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(), P(), P("data"), P("data")),
                         out_specs=(P(), P(), P()))(params, opt_state, x, y)

    for i in range(steps):
        params, opt_state, loss = step(params, opt_state, x, y)
        if i % 5 == 0 or i == steps - 1:
            print(f"step {i}: loss {float(loss):.5f}")
    return float(loss)


if __name__ == "__main__":
    main()

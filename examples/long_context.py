"""Long-context attention — the capabilities the reference does not have.

Three tools from the long-context layer on one script:

1. packed-varlen attention: several documents packed into one sequence
   with ``segment_ids`` (the TPU-native ``cu_seqlens``), masked blockwise
   inside the flash kernel;
2. ring attention: the sequence sharded across every local device, k/v
   chunks rotating over the ring;
3. Ulysses: the all-to-all re-shard alternative, head-parallel inside.

    python examples/long_context.py --seq-per-device 512
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.ops.flash_attention import flash_attention
from apex_tpu.transformer.context_parallel import (ring_attention,
                                                   ulysses_attention)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-per-device", type=int, default=256)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=32)
    args = ap.parse_args(argv)

    cp = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("context",))
    s = args.seq_per_device * cp
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, args.heads, s, args.head_dim),
                           jnp.float32) for _ in range(3))

    # 1. packed varlen: four documents in one sequence
    bounds = sorted(rng.choice(np.arange(1, s), 3, replace=False))
    ids = np.zeros((1, s), np.int32)
    for b in bounds:
        ids[0, b:] += 1
    packed = flash_attention(q, k, v, causal=True,
                             segment_ids=jnp.asarray(ids))
    print(f"packed-varlen over {s} tokens / 4 docs:",
          float(jnp.sum(packed ** 2)))

    spec = P(None, None, "context", None)

    def run(fn):
        return jax.jit(shard_map(
            lambda q, k, v: fn(q, k, v, "context", causal=True),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))(q, k, v)

    ring = run(ring_attention)
    print(f"ring attention over {cp} devices:", float(jnp.sum(ring ** 2)))
    if args.heads % cp == 0:
        uly = run(ulysses_attention)
        print("ulysses attention:", float(jnp.sum(uly ** 2)))
        np.testing.assert_allclose(np.asarray(ring), np.asarray(uly),
                                   rtol=2e-4, atol=2e-4)
        print("ring == ulysses == dense ✓")
    dense = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)
    return float(jnp.sum(ring ** 2))


if __name__ == "__main__":
    main()

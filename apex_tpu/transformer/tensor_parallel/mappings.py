"""TP collective mappings.

Reference: ``reference:apex/transformer/tensor_parallel/mappings.py`` — four
autograd Functions pairing a forward collective with its transpose:
``_CopyToModelParallelRegion`` (:79, identity fwd / allreduce bwd),
``_ReduceFromModelParallelRegion`` (:95, allreduce fwd / identity bwd),
``_ScatterToModelParallelRegion`` (:111, split fwd / allgather bwd),
``_GatherFromModelParallelRegion`` (:127, allgather fwd / split bwd).

TPU redesign: the reference hand-writes each backward because torch autograd
has no notion of device-variance. JAX's varying-manual-axes (VMA) type system
*is* that notion, and its transposes are exactly the Megatron pairs by
construction: the transpose of marking a value varying (``pcast
to='varying'``) is ``psum``, the transpose of ``psum`` is mark-varying, and
the transpose of a per-rank slice feeding a psum is the all-gather-sum. So
these mappings are thin forward-only wrappers and native AD produces the
reference's backward collectives with no custom_vjp — fewer moving parts and
correct for any input variance.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from jax.lax import axis_size as _axis_size

__all__ = [
    "copy_to_tensor_model_parallel_region",
    "reduce_from_tensor_model_parallel_region",
    "scatter_to_tensor_model_parallel_region",
    "gather_from_tensor_model_parallel_region",
]


def _vary(x):
    """Mark ``x`` device-varying over the tensor axis (idempotent; on
    pre-VMA jax the cast is an identity and shard_map's own replication
    rewrite supplies the transpose psum)."""
    from apex_tpu.utils.vma import cast_to_vma
    return cast_to_vma(x, frozenset({TENSOR_AXIS}))


def copy_to_tensor_model_parallel_region(x):
    """Identity forward; AD transpose of the vary-cast is the backward
    allreduce (:79-92)."""
    return _vary(x)


def reduce_from_tensor_model_parallel_region(x):
    """Allreduce forward; AD transpose of psum is the identity-as-varying
    backward (:95-108)."""
    return jax.lax.psum(_vary(x), TENSOR_AXIS)


def _split_local(x):
    tp = _axis_size(TENSOR_AXIS)
    if x.shape[-1] % tp:
        # a floor-divide here would silently drop the trailing
        # x.shape[-1] % tp elements on every rank
        raise ValueError(
            f"scatter_to_tensor_model_parallel_region: last dim of size "
            f"{x.shape[-1]} is not divisible by tensor parallel size {tp}")
    rank = jax.lax.axis_index(TENSOR_AXIS)
    chunk = x.shape[-1] // tp
    return jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk, axis=-1)


def scatter_to_tensor_model_parallel_region(x):
    """Keep-own-slice forward; transpose = gather of the slice cotangents
    (:111-124)."""
    return _split_local(_vary(x))


def gather_from_tensor_model_parallel_region(x):
    """All-gather along the last dim forward; transpose = reduce-scatter,
    which for the replicated cotangents of TP training is the reference's
    take-own-slice backward (:127-140)."""
    from apex_tpu.utils.vma import varying_all_gather
    return varying_all_gather(x, TENSOR_AXIS, axis=x.ndim - 1, tiled=True)

"""Vocab-parallel cross entropy.

Reference: ``reference:apex/transformer/tensor_parallel/cross_entropy.py:23-99``
— with logits sharded along vocab: local max → allreduce(MAX), local
predicted-logit (masked to the owning rank) and local sum-exp → allreduce(SUM),
then ``loss = log(sum_exp) - predicted_logit``; backward scales the local
softmax and subtracts the one-hot on the owning rank only.

Here the three collectives are ``pmax``/``psum`` over the ``tensor`` axis and
the backward falls out of AD with identical communication (the transpose of
psum/pmax touch the same axis).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from jax.lax import axis_size as _axis_size

__all__ = ["vocab_parallel_cross_entropy"]


def vocab_parallel_cross_entropy(vocab_parallel_logits: jnp.ndarray,
                                 target: jnp.ndarray,
                                 label_smoothing: float = 0.0) -> jnp.ndarray:
    """Per-token loss from vocab-sharded logits ``(..., vocab/tp)``.

    ``label_smoothing`` mirrors the reference's smoothing branch (kept 0 in
    the reference tests).
    """
    logits = vocab_parallel_logits.astype(jnp.float32)
    vp = logits.shape[-1]
    rank = jax.lax.axis_index(TENSOR_AXIS)
    start = rank * vp

    # numerically-stable global softmax pieces (:34-56); the max shift
    # cancels analytically in d(loss)/d(logits), so it is detached — which
    # also sidesteps pmax's missing transpose rule (the reference backward
    # :58-99 likewise treats it as a constant)
    local_max = jax.lax.stop_gradient(jnp.max(logits, axis=-1))
    global_max = jax.lax.pmax(local_max, TENSOR_AXIS)
    shifted = logits - global_max[..., None]
    sum_exp = jax.lax.psum(jnp.sum(jnp.exp(shifted), axis=-1), TENSOR_AXIS)

    # predicted logit: only the owning rank contributes (:40-52)
    in_range = (target >= start) & (target < start + vp)
    local_idx = jnp.where(in_range, target - start, 0)
    picked = jnp.take_along_axis(shifted, local_idx[..., None], axis=-1)[..., 0]
    predicted = jax.lax.psum(jnp.where(in_range, picked, 0.0), TENSOR_AXIS)

    loss = jnp.log(sum_exp) - predicted
    if label_smoothing > 0.0:
        # smoothing term needs mean of all logits: psum of local sums
        vocab_size = vp * _axis_size(TENSOR_AXIS)
        mean_logits = (jax.lax.psum(jnp.sum(shifted, axis=-1), TENSOR_AXIS)
                       / vocab_size)
        # loss = (1-s)*nll + s * (log_sum_exp - mean_logits)
        loss = (1.0 - label_smoothing) * loss + label_smoothing * (
            jnp.log(sum_exp) - mean_logits)
    return loss

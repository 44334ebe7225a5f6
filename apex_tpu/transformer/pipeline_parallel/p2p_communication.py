"""Stage-to-stage activation/grad exchange.

Reference: ``reference:apex/transformer/pipeline_parallel/p2p_communication.py``
— batched NCCL ``isend/irecv`` pairs (:29-67) behind 8 public ops
(:187-408), with an optional scatter-gather transport optimization that
splits tensors 1/tp_size during transit (:120-123,155-182) and a full
``cuda.synchronize`` after each batch (:166).

TPU redesign: under SPMD every stage executes the same program, so a
send/recv pair is one ``ppermute`` rotation over the ``pipe`` axis — XLA
lowers it to ICI neighbor DMA with no host sync. The scatter-gather
transport trick is subsumed by sharding the activation over ``tensor`` in
its sharding spec (GSPMD keeps it split in transit for free). The 8-op
surface collapses to two rotations; the reference names are kept as thin
aliases so schedule code reads the same.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.transformer.parallel_state import PIPE_AXIS
from jax.lax import axis_size as _axis_size

__all__ = [
    "rotate_forward", "rotate_backward",
    "send_forward_recv_forward", "send_backward_recv_backward",
]


def _perm_next(pp: int):
    return [(i, (i + 1) % pp) for i in range(pp)]


def _perm_prev(pp: int):
    return [(i, (i - 1) % pp) for i in range(pp)]


def rotate_forward(x: jnp.ndarray) -> jnp.ndarray:
    """Every stage sends ``x`` to the next stage and receives from the
    previous (wrapping; the wrap value is ignored by stage 0's select in the
    schedules). ``send_forward`` + ``recv_forward`` of the reference."""
    pp = _axis_size(PIPE_AXIS)
    return jax.lax.ppermute(x, PIPE_AXIS, _perm_next(pp))


def rotate_backward(g: jnp.ndarray) -> jnp.ndarray:
    """``send_backward`` + ``recv_backward``: grads flow to the previous
    stage."""
    pp = _axis_size(PIPE_AXIS)
    return jax.lax.ppermute(g, PIPE_AXIS, _perm_prev(pp))


# reference-named aliases (p2p_communication.py:187-408)
send_forward_recv_forward = rotate_forward
send_backward_recv_backward = rotate_backward

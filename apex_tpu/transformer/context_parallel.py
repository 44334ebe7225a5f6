"""Context parallelism: ring attention + sequence-parallel mappings.

The reference has **no** long-sequence distribution (SURVEY §5: no ring
attention, no context-parallel group, no Ulysses all-to-all; its fused
attention caps at seqlen 2048/512). On TPU long context is first-class,
so this module goes beyond parity:

- :func:`ring_attention` — blockwise-softmax attention with the sequence
  sharded over a mesh axis: each device holds its (b, h, s/cp, d) shard,
  k/v chunks rotate around the ring via ``ppermute`` (ICI
  neighbor-to-neighbor traffic, the ideal TPU collective), and the online
  (m, l, acc) running softmax merges chunks exactly — the Ring Attention
  construction. Causality is handled per chunk-origin: earlier chunks
  attend fully, the diagonal chunk causally, later chunks not at all
  (their work is skipped numerically via masking; the rotation itself is
  uniform, keeping the program SPMD). Backward falls out of AD through the
  scan — the transpose of ``ppermute`` is the reverse rotation, so
  gradients ride the same ring. ``remat=True`` (default) recomputes each
  chunk's scores in backward: residuals stay O(s_local·d), never
  O(s_local·s_global).
- sequence-parallel scatter/gather (Megatron-LM SP): norms/dropout run on
  a 1/tp sequence shard between the TP collectives. On TPU these are thin
  ``ppermute``-free wrappers over all_gather/psum_scatter along the
  sequence dim of the TENSOR axis.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from apex_tpu.ops.flash_attention import NEG_INF
from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from jax.lax import axis_size as _axis_size

__all__ = ["ring_attention", "ulysses_attention",
           "scatter_to_sequence_parallel_region",
           "gather_from_sequence_parallel_region",
           "reduce_scatter_to_sequence_parallel_region"]


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = False,
                   softmax_scale: Optional[float] = None,
                   remat: bool = True) -> jnp.ndarray:
    """Attention over a sequence sharded on ``axis_name``.

    ``q``/``k``/``v``: this device's shard, ``(b, h, s_local, d)``, where
    the global sequence is the rank-order concatenation of shards. Must be
    called inside ``shard_map`` with ``axis_name`` bound. Returns the
    output shard ``(b, h, s_local, d)``.

    Chunk math runs in fp32 (scores + running stats), inputs may be bf16.
    """
    b, h, s_loc, d = q.shape
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(d)
    cp = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % cp) for i in range(cp)]

    qf = q.astype(jnp.float32)

    def chunk_update(carry, kv_and_t):
        m, l, acc = carry
        k_c, v_c, t = kv_and_t
        # after t rotations this device holds the chunk that originated on
        # rank (rank - t) mod cp
        kv_rank = jax.lax.rem(rank - t + cp, cp)
        s = jax.lax.dot_general(
            qf, k_c.astype(jnp.float32),
            (((3,), (3,)), ((0, 1), (0, 1)))) * softmax_scale
        if causal:
            row = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (s_loc, s_loc), 1)
            in_chunk = col <= row                      # diagonal chunk
            allowed = jnp.where(
                kv_rank < rank, True,
                jnp.where(kv_rank > rank, False, in_chunk))
            s = jnp.where(allowed, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            # fully-masked chunks drive m_new to NEG_INF -> exp == 1 garbage
            p = jnp.where(allowed, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p, v_c.astype(jnp.float32), (((3,), (2,)), ((0, 1), (0, 1))))
        return (m_new, l, acc)

    if remat:
        chunk_update = jax.checkpoint(chunk_update)

    def body(carry, t):
        m, l, acc, k_c, v_c = carry
        m, l, acc = chunk_update((m, l, acc), (k_c, v_c, t))
        # rotate kv to the next device for the following step (uniform —
        # also on the last step, keeping the scan body SPMD-identical;
        # the final rotation returns each chunk home)
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        return (m, l, acc, k_c, v_c), None

    from apex_tpu.utils.vma import cast_to_vma
    vma = frozenset({axis_name})
    init = (cast_to_vma(jnp.full((b, h, s_loc, 1), NEG_INF, jnp.float32),
                        vma),
            cast_to_vma(jnp.zeros((b, h, s_loc, 1), jnp.float32), vma),
            cast_to_vma(jnp.zeros((b, h, s_loc, d), jnp.float32), vma),
            k, v)
    (m, l, acc, _, _), _ = jax.lax.scan(body, init, jnp.arange(cp))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    return (acc / safe_l).astype(q.dtype)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      axis_name: str, causal: bool = False,
                      softmax_scale: Optional[float] = None,
                      attention_fn=None) -> jnp.ndarray:
    """DeepSpeed-Ulysses context parallelism: two ``all_to_all``s instead
    of a ring.

    Input/output layout matches :func:`ring_attention` — ``(b, h, s/cp,
    d)`` sequence shards. Internally the first ``all_to_all`` re-shards
    from sequence-split to *head*-split (each device gets ``h/cp`` full-
    sequence heads), runs ordinary full-sequence attention per local head
    (``attention_fn``, default the fused flash/XLA dispatcher — so the
    Pallas kernel runs on full sequences), and the second ``all_to_all``
    restores sequence sharding. Requires ``h % cp == 0``; for more devices
    than heads use :func:`ring_attention`. Ulysses moves O(b·s·d·h/cp) per
    all_to_all but keeps the attention kernel monolithic; the ring keeps
    traffic neighbor-to-neighbor but chunks the kernel — the standard
    trade, both offered here.
    """
    b, h_loc_in, s_loc, d = q.shape
    cp = _axis_size(axis_name)
    # note: h here is the LOCAL head count of the sequence-sharded layout,
    # which equals the global head count (heads are replicated across cp)
    if h_loc_in % cp:
        raise ValueError(f"num heads {h_loc_in} not divisible by cp={cp}")
    if attention_fn is None:
        from apex_tpu.ops.flash_attention import flash_attention
        attention_fn = flash_attention

    def seq_to_heads(x):
        # (b, h, s/cp, d) -> (b, h/cp, s, d): each device keeps its head
        # slice, receives the full sequence (tiled all_to_all splits axis 1
        # by cp and concatenates received chunks along axis 2 in device —
        # i.e. sequence — order)
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = attention_fn(qh, kh, vh, causal=causal,
                       softmax_scale=softmax_scale)
    return heads_to_seq(out)


# ---------------------------------------------------------------------------
# Megatron-LM sequence parallelism (norms/dropout on sequence shards)
# ---------------------------------------------------------------------------

def scatter_to_sequence_parallel_region(x: jnp.ndarray,
                                        axis_name: str = TENSOR_AXIS,
                                        seq_axis: int = 0) -> jnp.ndarray:
    """Split the sequence dim across the TP axis (fwd); gather in bwd.
    Entering an SP region (Megatron-LM ``scatter_to_sequence_parallel``;
    the reference layout is (s, b, h) so ``seq_axis`` defaults to 0 —
    pass 1 for (b, s, h) models)."""
    tp = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    if x.shape[seq_axis] % tp:
        raise ValueError(f"sequence dim {x.shape[seq_axis]} not divisible "
                         f"by tp={tp}")
    chunk = x.shape[seq_axis] // tp
    return jax.lax.dynamic_slice_in_dim(x, rank * chunk, chunk,
                                        axis=seq_axis)


def gather_from_sequence_parallel_region(x: jnp.ndarray,
                                         axis_name: str = TENSOR_AXIS,
                                         seq_axis: int = 0,
                                         invariant: bool = False
                                         ) -> jnp.ndarray:
    """all_gather the sequence shards (fwd); reduce-scatter in bwd. Leaving
    an SP region into a TP matmul.

    ``invariant=True`` types the gathered result device-invariant (every
    rank provably holds the same full sequence). Inside a TP model this
    matters for AD bookkeeping: plain-TP activations are invariant, so the
    SP gather must restore that type or replicated-parameter cotangents
    get attributed per-rank and differ from the TP=1 semantics (see
    tests/test_models.py::test_gpt_sequence_parallel_matches_tp)."""
    if not invariant:
        from apex_tpu.utils.vma import varying_all_gather
        return varying_all_gather(x, axis_name, axis=seq_axis, tiled=True)
    from apex_tpu.utils.vma import invariant_all_gather
    return invariant_all_gather(x, axis_name, axis=seq_axis)


def reduce_scatter_to_sequence_parallel_region(x: jnp.ndarray,
                                               axis_name: str = TENSOR_AXIS,
                                               seq_axis: int = 0
                                               ) -> jnp.ndarray:
    """psum_scatter along the sequence dim — the RowParallel output path
    under SP (replaces the plain psum: each rank keeps only its sequence
    shard of the reduced activations)."""
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=seq_axis,
                                tiled=True)

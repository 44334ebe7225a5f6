"""Ring-decomposed collective matmuls: overlap TP collectives with the
GEMMs that consume them.

Reference: ``reference:apex/transformer/tensor_parallel/layers.py:259-374``
(``LinearWithGradAccumulationAndAsyncAllreduce``) hides TP communication
behind compute by hand-rolling async NCCL handles. Our layers' docstring
notes XLA's latency-hiding scheduler does that for free — but only for
*independent* collectives. The sequence-parallel hot path is a **dependent**
pair: ColumnParallel all-gathers the sequence and immediately feeds the
GEMM; RowParallel's GEMM immediately feeds a reduce-scatter. A monolithic
``all_gather``/``psum_scatter`` cannot start or finish under the GEMM it is
glued to, so every transformer block exposes one full ICI latency each way.

The fix (Wang et al., "Overlapping Communication with Dependent Computation
via Decomposition in Large Deep Learning Models", ASPLOS 2023; also
Megatron-LM's ``tp_comm_overlap``) is to decompose both ops into ``tp``
ring steps of ``lax.ppermute`` + a partial ``dot_general``:

- :func:`all_gather_matmul` (``AG ⊗ matmul``): each rank starts from its
  own sequence chunk, GEMMs it, and ppermutes it to the next rank — chunk
  *k*'s transfer is independent of chunk *k−1*'s GEMM, so the scheduler
  rides the transfer under the GEMM. After ``tp−1`` hops every rank has
  computed the full-sequence product without ever materializing a fused
  all-gather.
- :func:`matmul_reduce_scatter` (``matmul ⊗ RS``): a partial-sum
  accumulator travels the ring; at each stop the local rank GEMMs the
  sequence chunk the accumulator is destined for and adds it. The incoming
  ``ppermute`` overlaps the local GEMM. The accumulator visits ranks in a
  **fixed ring order**, so the fp32 accumulation order is deterministic
  (``psum_scatter``'s order is backend-defined); at tp=2 a two-term fp32
  sum is commutative, so in fp32 compute the result is bit-identical to
  the fused path. (Under bf16 compute the ring is *better*, not
  bit-equal: it accumulates in fp32 end-to-end where the fused path casts
  each rank's partial to bf16 before the reduction.)

Both carry a ``custom_vjp`` whose backward uses the *transposed*
decomposition — the reduce-scatter of dX rides under the dW GEMM (the
exact win of apex's async-allreduce backward), and the all-gather of dY
rides under its own partial GEMMs:

    all_gather_matmul:    dX = RS(dY @ W)  (ring) ∥ dW = dYᵀ @ AG(X)
    matmul_reduce_scatter: dX = AG(dY) @ W (ring) ∥ dW = AG(dY)ᵀ @ X

so forward AND backward overlap. ``X_full`` (the gathered activations) is
assembled for free from the ring's received chunks and saved as the
residual — tp× the shard's memory, the classic Megatron trade (re-gathering
in backward would re-serialize the dW GEMM behind a collective).

Everything here is plain SPMD code (``ppermute`` + ``dot_general``) — it
runs inside ``shard_map`` on any jax version, pre-VMA 0.4.x included; the
backward rules are written explicitly so no VMA replication rewrite is
needed for correctness.

Telemetry: ``tp/overlap_chunks`` and ``tp/collective_bytes`` are recorded
at the *model* level (``GPTModel.transform``), not here — these functions
are traced by the ``custom_vjp`` machinery (and often inside a layer
``lax.scan``), where an :mod:`apex_tpu.observability.ingraph` record would
capture tracers from the wrong trace level and count one scan-body trace
instead of ``num_layers`` executions.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from jax.lax import axis_size as _axis_size
from apex_tpu.utils.vma import cast_to_vma, reconcile_cotangent

__all__ = ["all_gather_matmul", "matmul_reduce_scatter"]


def _dims_last(a_ndim: int, w_axis: int):
    """Contract ``a``'s last dim with ``w``'s ``w_axis`` dim (no batch)."""
    return (((a_ndim - 1,), (w_axis,)), ((), ()))


def _ring_all_gather_matmul(x: jnp.ndarray, w: jnp.ndarray, axis_name: str,
                            seq_axis: int, w_axis: int
                            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``AG(x, seq_axis) · w`` decomposed into ``tp`` {ppermute, dot} pairs.

    ``x``: this rank's sequence chunk; ``w``: this rank's weight shard,
    contracted ``x[..., -1] × w[w_axis]``. Returns ``(y_full, x_full)``:
    the full-sequence product (fp32, MXU accumulation) and the gathered
    operand (assembled from the received chunks, ``x.dtype``) for use as a
    backward residual. Issues exactly ``tp−1`` ppermutes; each hop is
    independent of the same step's partial GEMM, which is what lets XLA's
    latency-hiding scheduler overlap them.
    """
    tp = _axis_size(axis_name)
    x = cast_to_vma(x, frozenset({axis_name}))
    rank = jax.lax.axis_index(axis_name)
    s_loc = x.shape[seq_axis]
    perm = [(i, (i + 1) % tp) for i in range(tp)]
    dims = _dims_last(x.ndim, w_axis)

    cur = x
    y_full = x_full = None
    for t in range(tp):
        # after t hops this rank holds the chunk that originated on rank-t
        origin = jax.lax.rem(rank - t + tp, tp)
        part = jax.lax.dot_general(cur, w, dims,
                                   preferred_element_type=jnp.float32)
        if y_full is None:
            y_shape = list(part.shape)
            y_shape[seq_axis] = tp * s_loc
            y_full = cast_to_vma(jnp.zeros(y_shape, jnp.float32),
                                 frozenset({axis_name}))
            x_shape = list(cur.shape)
            x_shape[seq_axis] = tp * s_loc
            x_full = cast_to_vma(jnp.zeros(x_shape, cur.dtype),
                                 frozenset({axis_name}))
        start = origin * s_loc
        y_full = jax.lax.dynamic_update_slice_in_dim(y_full, part, start,
                                                     axis=seq_axis)
        x_full = jax.lax.dynamic_update_slice_in_dim(x_full, cur, start,
                                                     axis=seq_axis)
        if t < tp - 1:
            cur = jax.lax.ppermute(cur, axis_name, perm)
    return y_full, x_full


def _ring_matmul_reduce_scatter(x: jnp.ndarray, w: jnp.ndarray,
                                axis_name: str, seq_axis: int, w_axis: int,
                                partial_add: Optional[jnp.ndarray] = None
                                ) -> jnp.ndarray:
    """``RS_seq(x · w [+ partial_add])`` as a ring of partial GEMMs.

    ``x``: full-sequence local operand (each rank a different partial
    product term); returns this rank's sequence shard of the rank-sum
    (fp32). The accumulator for chunk ``c`` starts on rank ``c+1`` and
    visits ranks in ring order, ending at its owner — ``tp−1`` ppermutes,
    each overlapping the next stop's partial GEMM, and a deterministic
    fp32 accumulation order fixed by ring position.
    """
    tp = _axis_size(axis_name)
    x = cast_to_vma(x, frozenset({axis_name}))
    rank = jax.lax.axis_index(axis_name)
    s_full = x.shape[seq_axis]
    if s_full % tp:
        raise ValueError(
            f"matmul_reduce_scatter: dim {seq_axis} of size {s_full} is not "
            f"divisible by {axis_name!r} axis size {tp}")
    s_loc = s_full // tp
    perm = [(i, (i + 1) % tp) for i in range(tp)]
    dims = _dims_last(x.ndim, w_axis)

    acc = None
    for t in range(tp):
        # this rank is stop t of the chunk destined for rank - t - 1
        c = jax.lax.rem(rank - t - 1 + 2 * tp, tp)
        chunk = jax.lax.dynamic_slice_in_dim(x, c * s_loc, s_loc,
                                             axis=seq_axis)
        part = jax.lax.dot_general(chunk, w, dims,
                                   preferred_element_type=jnp.float32)
        if partial_add is not None:
            part = part + partial_add.astype(jnp.float32)
        if acc is None:
            acc = part
        else:
            acc = jax.lax.ppermute(acc, axis_name, perm) + part
    return acc


# ---------------------------------------------------------------------------
# public primitives (custom_vjp: fwd AND bwd are ring-decomposed)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def all_gather_matmul(x: jnp.ndarray, w_t: jnp.ndarray,
                      axis_name: str = TENSOR_AXIS,
                      seq_axis: int = 1) -> jnp.ndarray:
    """``all_gather(x, seq_axis) @ w_t.T`` with the gather ring-decomposed
    under the partial GEMMs — the sequence-parallel ColumnParallel forward.

    ``x``: ``(..., s_local, ..., in)`` sequence shard over ``axis_name``;
    ``w_t``: ``(out, in)`` weight shard (torch layout). Returns the
    full-sequence ``(..., tp*s_local, ..., out)`` product in fp32 (same
    MXU-accumulation contract as the fused path — cast at the call site).
    Backward: ``dX = RS_seq(dY @ W)`` ring-decomposed, overlapping the
    single ``dW = dYᵀ @ AG(X)`` GEMM (the async-allreduce-backward win).
    """
    y, _ = _ring_all_gather_matmul(x, w_t, axis_name, seq_axis, w_axis=1)
    return y


def _ag_mm_fwd(x, w_t, axis_name, seq_axis):
    y, x_full = _ring_all_gather_matmul(x, w_t, axis_name, seq_axis,
                                        w_axis=1)
    return y, (w_t, x_full)


def _ag_mm_bwd(axis_name, seq_axis, res, dy):
    w_t, x_full = res
    # dX: (…, s_full, out)·(out, in) -> shard — ring reduce-scatter of the
    # input cotangents, each hop riding under the next partial GEMM
    dx = _ring_matmul_reduce_scatter(dy, w_t, axis_name, seq_axis, w_axis=0)
    dx = dx.astype(x_full.dtype)
    # dW: one dense GEMM over the saved gathered activations — independent
    # of the dX ring, so the scheduler overlaps the two
    bdims = tuple(range(dy.ndim - 1))
    dw = jax.lax.dot_general(dy, x_full.astype(jnp.float32),
                             ((bdims, bdims), ((), ())),
                             preferred_element_type=jnp.float32)
    # x_full carries x's varying-axes set (built from x via the ring), so it
    # stands in for the primal in the VMA reconciliation (no-op pre-VMA)
    return (reconcile_cotangent(dx, x_full),
            reconcile_cotangent(dw.astype(w_t.dtype), w_t))


all_gather_matmul.defvjp(_ag_mm_fwd, _ag_mm_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def matmul_reduce_scatter(x: jnp.ndarray, w_t: jnp.ndarray,
                          partial_add: Optional[jnp.ndarray] = None,
                          axis_name: str = TENSOR_AXIS,
                          seq_axis: int = 1) -> jnp.ndarray:
    """``reduce_scatter(x @ w_t.T [+ partial_add], seq_axis)`` with the
    reduction ring-decomposed under the partial GEMMs — the
    sequence-parallel RowParallel forward.

    ``x``: ``(..., s_full, ..., in_local)`` full-sequence local operand;
    ``w_t``: ``(out, in_local)`` shard; ``partial_add``: optional
    ``(out,)``-broadcastable term added to every rank's partial *before*
    the reduction (the RowParallel bias fold — each of the ``tp`` partials
    carries ``b/tp`` so the ring sum restores ``b`` exactly once, and its
    cotangent is the full-sequence sum on every rank, matching the fused
    path's semantics on any jax version). Returns this rank's
    ``(..., s_full/tp, ..., out)`` shard of the sum in fp32, accumulation
    order fixed by ring position (in fp32 compute: bit-identical to
    ``psum_scatter`` at tp=2, ≤1-ULP reordering beyond; in bf16 compute
    the fused path reduces in bf16 while this stays fp32 — better, not
    bit-equal).
    Backward: ``dX = AG(dY) @ W`` ring-decomposed; the gathered ``dY``
    falls out of the same ring and feeds the dW GEMM.
    """
    return _ring_matmul_reduce_scatter(x, w_t, axis_name, seq_axis,
                                       w_axis=1, partial_add=partial_add)


def _mm_rs_fwd(x, w_t, partial_add, axis_name, seq_axis):
    y = _ring_matmul_reduce_scatter(x, w_t, axis_name, seq_axis, w_axis=1,
                                    partial_add=partial_add)
    return y, (x, w_t, None if partial_add is None else partial_add)


def _mm_rs_bwd(axis_name, seq_axis, res, dy):
    x, w_t, partial_add = res
    # dX: AG_seq(dY)·(out, in) — ring-decomposed; dy_full assembles from the
    # received chunks for free
    dx, dy_full = _ring_all_gather_matmul(dy, w_t, axis_name, seq_axis,
                                          w_axis=0)
    dx = dx.astype(x.dtype)
    bdims = tuple(range(x.ndim - 1))
    dw = jax.lax.dot_general(dy_full, x.astype(jnp.float32),
                             ((bdims, bdims), ((), ())),
                             preferred_element_type=jnp.float32)
    if partial_add is None:
        d_add = None
    else:
        # every rank's partial carried partial_add at every position, and
        # rank c's output chunk is the cotangent of each rank's partial at
        # that chunk — so the per-rank cotangent is the broadcast-transpose
        # of dY_full (identical on every rank; no collective needed): sum
        # over every axis partial_add was broadcast along, right-aligned
        padded = ((1,) * (dy_full.ndim - jnp.ndim(partial_add))
                  + jnp.shape(partial_add))
        axes = tuple(i for i, n in enumerate(padded) if n == 1)
        d_add = jnp.sum(dy_full, axis=axes).reshape(
            jnp.shape(partial_add)).astype(partial_add.dtype)
        d_add = reconcile_cotangent(d_add, partial_add)
    return (reconcile_cotangent(dx, x),
            reconcile_cotangent(dw.astype(w_t.dtype), w_t), d_add)


matmul_reduce_scatter.defvjp(_mm_rs_fwd, _mm_rs_bwd)

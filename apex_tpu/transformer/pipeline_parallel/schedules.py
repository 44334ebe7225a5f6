"""Pipeline-parallel forward/backward schedules.

Reference: ``reference:apex/transformer/pipeline_parallel/schedules/`` —
``get_forward_backward_func`` (:__init__.py:22) dispatching between
no-pipelining (:fwd_bwd_no_pipelining.py:31-103), 1F1B without interleaving
(:fwd_bwd_pipelining_without_interleaving.py:155-345) and interleaved
virtual-pipeline 1F1B (:fwd_bwd_pipelining_with_interleaving.py:25-375).

TPU redesign. The reference drives each microbatch's fwd/bwd from Python
with explicit NCCL p2p — impossible and unnecessary under jit. Here a
schedule is a *traced program*: a ``lax.scan`` over pipeline ticks inside
``shard_map`` over the ``pipe`` axis, with one ``ppermute`` rotation per
tick. Two backward drivers exist:

* the DEFAULT (``memory_efficient=True``, :func:`_onef1b_fwd_bwd`): one
  scan whose tick runs one forward AND one backward microbatch per global
  stage via explicit ``jax.vjp`` with recompute — the true 1F1B memory
  bound, O(pp·vpp) in-flight activations regardless of microbatch count
  (the role of the reference's interleaved fwd/bwd +
  ``free_output_tensor``, :schedules/common.py:198-249);
* the AD driver (``memory_efficient=False``): differentiating the
  forward tick scan yields the backward pipeline automatically (the
  transpose of ``ppermute`` is the reverse rotation; the reversed scan
  replays the cooldown/steady/warmup structure) — the reference's
  340-line warmup/steady/cooldown bookkeeping as autodiff. Residuals are
  O(ticks) per stage; ``remat=True`` shrinks each tick's residual to the
  carry.

The stage function must be *stage-uniform* (same jaxpr on every device) and
branch on the traced stage index for first/last specifics — the SPMD analog
of ``build_model``'s pre_process/post_process flags
(:schedules/common.py:29-148).

Microbatch m enters stage 0 at tick m and exits stage S-1 (chunk vpp-1) at
tick m + L - 1 (L = S*vpp global stages); total ticks = M + L - 1. Bubble
ticks process zeros and are masked out of the loss.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.observability import ingraph as _metrics
from apex_tpu.remat import RematPolicy
from apex_tpu.transformer.parallel_state import PIPE_AXIS
from apex_tpu.utils.vma import cast_to_vma
from apex_tpu.transformer.pipeline_parallel.p2p_communication import (
    rotate_backward, rotate_forward)
from jax.lax import axis_size as _axis_size


def _record_schedule_metrics(num_microbatches: int, ticks: int,
                             useful_ticks: int) -> None:
    """Static schedule shape telemetry (trace-time Python constants — free
    even when a collector is active, absent when not). ``bubble_fraction``
    is the analytic idle share of stage time slots: each of ``ticks``
    slots per stage runs at most one microbatch unit of useful work, of
    which ``useful_ticks`` are non-bubble — Megatron's (p-1)/(m+p-1) for
    the forward pipe, (2p-1)/(m+2p-1) for the fwd+bwd 1F1B scan. Per-tick
    *wall* times are a trace concern: the ``pipeline_tick`` named_scope
    labels every tick's fusions in a ``profile_trace`` capture."""
    _metrics.record("pipeline/num_microbatches", float(num_microbatches),
                    reduce="mean")
    _metrics.record("pipeline/ticks", float(ticks), reduce="mean")
    _metrics.record("pipeline/bubble_fraction",
                    1.0 - useful_ticks / ticks, reduce="mean")




__all__ = [
    "get_forward_backward_func",
    "forward_backward_no_pipelining",
    "forward_backward_pipelining_without_interleaving",
    "forward_backward_pipelining_with_interleaving",
    "pipelined_apply",
]


# ---------------------------------------------------------------------------
# no pipelining: scan over microbatches, grad accumulation
# ---------------------------------------------------------------------------

def forward_backward_no_pipelining(
    forward_step_func: Callable,
    batch: Any,
    params: Any,
    *,
    forward_only: bool = False,
    grad_scale: Any = 1.0,
    loss_fn: Optional[Callable] = None,
    num_model_chunks: Optional[int] = None,
    remat: Any = False,
) -> Tuple[jnp.ndarray, Any]:
    """``fwd_bwd_no_pipelining.py:31-103``: loop microbatches, accumulate.

    ``forward_step_func(params, microbatch) -> loss`` (scalar, already
    averaged over the microbatch). ``batch`` is a pytree whose leaves have a
    leading ``num_microbatches`` axis (see
    :func:`~apex_tpu.transformer.pipeline_parallel.utils.get_kth_microbatch`
    for slicing helpers). Returns ``(mean_loss, grads_or_None)``; grads are
    averaged over microbatches, matching the reference's grad-sync-at-end
    semantics (the no_sync context of :77-85 — accumulation happens locally,
    one sync afterwards by the caller's DDP).

    When ``loss_fn`` is given, the *pipelined* call shape is accepted instead
    so :func:`get_forward_backward_func` call sites are uniform across
    pipeline sizes: ``forward_step_func(params, x, stage_index)`` is the
    whole model (the single stage of a pp=1 run), applied microbatch-wise,
    and ``loss_fn(y, m)`` the head. ``num_model_chunks`` must then be None
    or 1.
    """
    if loss_fn is not None:
        if num_model_chunks not in (None, 1):
            raise ValueError("pp=1 runs have a single model chunk")
        stage_fn = RematPolicy.resolve(remat).wrap(forward_step_func)

        def uniform_step(params, mb_with_index):
            mb, m = mb_with_index
            return loss_fn(stage_fn(params, mb, 0), m)

        n = jax.tree_util.tree_leaves(batch)[0].shape[0]
        batch = (batch, jnp.arange(n))
        forward_step_func = uniform_step

    def one(params, mb):
        if forward_only:
            return forward_step_func(params, mb), None
        loss, grads = jax.value_and_grad(
            lambda p: forward_step_func(p, mb) * grad_scale)(params)
        return loss / grad_scale, grads

    def scan_body(acc, mb):
        loss, grads = one(params, mb)
        acc_loss, acc_grads = acc
        if grads is not None:
            acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads, grads)
        return (acc_loss + loss, acc_grads), None

    n_micro = jax.tree_util.tree_leaves(batch)[0].shape[0]
    # pp=1: every tick is useful — reported so the stream's pipeline/*
    # keys exist across schedule choices
    _record_schedule_metrics(n_micro, n_micro, n_micro)
    zero_grads = None if forward_only else jax.tree_util.tree_map(
        lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params)
    (total_loss, total_grads), _ = jax.lax.scan(
        scan_body, (jnp.asarray(0.0, jnp.float32), zero_grads), batch)
    mean_loss = total_loss / n_micro
    if forward_only:
        return mean_loss, None
    grads = jax.tree_util.tree_map(
        lambda g: (g / (n_micro * grad_scale)).astype(jnp.float32), total_grads)
    return mean_loss, grads


# ---------------------------------------------------------------------------
# pipelined forward (shared by both pipelined schedules)
# ---------------------------------------------------------------------------

def pipelined_apply(
    stage_fn: Callable,
    stage_params: Any,
    microbatches: jnp.ndarray,
    *,
    num_chunks: int = 1,
    remat: Any = False,
    last_stage_fn: Optional[Callable] = None,
    embed_fn: Optional[Callable] = None,
) -> jnp.ndarray:
    """Run ``microbatches`` through the virtual pipeline; returns the
    per-microbatch outputs of the final global stage, shape ``(M, ...)``.

    Must be called inside ``shard_map`` with the ``pipe`` axis bound.

    - ``stage_fn(chunk_params, x, global_stage) -> y`` — uniform stage body;
      ``global_stage`` is a traced int in ``[0, S*num_chunks)``.
    - ``stage_params``: pytree whose leaves are stacked ``(num_chunks, ...)``
      — this device's chunks (Megatron layout: chunk c on device d is global
      stage ``c*S + d``,
      ``fwd_bwd_pipelining_with_interleaving.py:122-131``).
    - ``microbatches``: ``(M, ...)`` fed to global stage 0; activations keep
      this trailing shape through every stage unless ``embed_fn`` maps them
      first.
    - ``last_stage_fn(y, m_index) -> out`` — applied to the final stage's
      output (e.g. loss head); defaults to identity.
    - ``embed_fn(microbatch) -> activation`` — the first-stage input
      transform (e.g. token embedding), the ``pre_process`` role of
      ``build_model`` (:schedules/common.py:29-148). With it, microbatches
      may have any shape/dtype (e.g. int tokens); the pipelined activation
      is ``embed_fn``'s output. Under SPMD every rank traces the embed (the
      program is stage-uniform) and only stage 0's result is consumed — the
      lookup is negligible next to a transformer stage.

    **Memory profile (measured, see tests/test_pipeline_memory.py).** This
    schedule is *output*-equivalent to the reference's 1F1B, not
    memory-equivalent: AD of the tick scan stores residuals for every tick,
    so backward activation memory is **O((M + L) per-tick residual)** per
    device, while the reference's interleaved fwd/bwd
    (``fwd_bwd_pipelining_without_interleaving.py:155-345``) keeps at most
    O(L) microbatches in flight. What ``remat=True`` guarantees: each
    tick's residual shrinks to the carry (one activation per local chunk) —
    intra-stage activations are recomputed in backward — measured ~4x per-
    microbatch reduction on a 3-matmul stage and exactly the
    carry-per-tick bound asserted in the test. For memory-bound configs
    keep M modest per call (grad-accumulate across calls) or pass
    ``remat=True``.
    """
    S = _axis_size(PIPE_AXIS)
    rank = jax.lax.axis_index(PIPE_AXIS)
    M = jax.tree_util.tree_leaves(microbatches)[0].shape[0]
    L = S * num_chunks
    T = M + L - 1
    _record_schedule_metrics(M, T, M)
    # bool | mode string | RematPolicy — "full" (== the legacy True) is
    # plain jax.checkpoint; the name-based policies save/offload the
    # registry-tagged activations the stage_fn emits (apex_tpu/remat.py)
    remat_fn = RematPolicy.resolve(remat).wrap(stage_fn)
    if embed_fn is None:
        if not isinstance(microbatches, jnp.ndarray):
            raise ValueError(
                "pytree microbatches require embed_fn to map them to the "
                "pipelined activation")
        act_shape = microbatches.shape[1:]
        act_dtype = microbatches.dtype
    else:
        mb0 = jax.tree_util.tree_map(
            lambda v: jax.lax.index_in_dim(v, 0, 0, keepdims=False),
            microbatches)
        act_aval = jax.eval_shape(embed_fn, mb0)
        act_shape, act_dtype = act_aval.shape, act_aval.dtype

    def chunk_params_at(c: int):
        return jax.tree_util.tree_map(
            lambda p: jax.lax.index_in_dim(p, c, 0, keepdims=False),
            stage_params)

    @jax.named_scope("pipeline_tick")
    def tick(buf, t):
        # buf: (num_chunks, *act_shape) — input activation per local chunk
        outs = []
        for c in range(num_chunks):
            x = buf[c]
            if c == 0:
                # global stage 0 = device 0 chunk 0 consumes fresh microbatch
                fresh = jax.tree_util.tree_map(
                    lambda v: jax.lax.dynamic_index_in_dim(
                        v, jnp.clip(t, 0, M - 1), 0, keepdims=False),
                    microbatches)
                if embed_fn is not None:
                    fresh = embed_fn(fresh)
                x = jnp.where(rank == 0, fresh.astype(act_dtype), x)
            g_stage = c * S + rank
            y = remat_fn(chunk_params_at(c), x, g_stage)
            outs.append(y.astype(act_dtype))
        stacked = jnp.stack(outs)  # (num_chunks, *act_shape)
        # rotate all chunk outputs to the next device
        received = rotate_forward(stacked)
        # wrap rule: device 0's chunk c>0 consumes last device's chunk c-1
        new_buf = [jnp.zeros(act_shape, act_dtype)] * num_chunks
        for c in range(num_chunks):
            if c == 0:
                new_buf[0] = received[0]  # overwritten by fresh on rank 0
            else:
                new_buf[c] = jnp.where(rank == 0, received[c - 1], received[c])
        # final-stage output this tick (device S-1, chunk num_chunks-1)
        final_out = outs[num_chunks - 1]
        return jnp.stack(new_buf), final_out

    # fixed-point the carry's varying-axes set: the stage body may add axes
    # (e.g. a TP bias makes activations tensor-varying)
    zeros = jnp.zeros((num_chunks,) + act_shape, act_dtype)
    carry_vma = frozenset({PIPE_AXIS})
    for _ in range(4):
        init = cast_to_vma(zeros, carry_vma)
        out_vma = getattr(jax.eval_shape(
            lambda b: tick(b, jnp.asarray(0))[0], init), "vma", frozenset())
        if out_vma <= carry_vma:
            break
        carry_vma = carry_vma | out_vma

    def tick_stable(buf, t):
        new_buf, final_out = tick(buf, t)
        return cast_to_vma(new_buf, carry_vma), final_out

    _, final_outs = jax.lax.scan(tick_stable, init, jnp.arange(T))

    # final stage emits microbatch m at tick m + L - 1; broadcast the last
    # device's outputs over the pipe axis (masked psum) so every stage
    # returns the same — replicated — result
    outs = jax.lax.dynamic_slice_in_dim(final_outs, L - 1, M, axis=0)
    outs = jax.lax.psum(jnp.where(rank == S - 1, outs, jnp.zeros_like(outs)),
                        PIPE_AXIS)
    if last_stage_fn is not None:
        outs = jax.vmap(last_stage_fn)(outs, jnp.arange(M))
    return outs


# ---------------------------------------------------------------------------
# memory-efficient 1F1B: hand-driven vjp inside the tick scan
# ---------------------------------------------------------------------------

from apex_tpu.utils.vma import fixed_point_vma as _fixed_point_vma
from apex_tpu.utils.vma import leaf_vma as _leaf_vma


def _onef1b_fwd_bwd(stage_fn, loss_fn, params, microbatches, remat,
                    grad_scale, shared_params=None, embed_fn=None,
                    num_chunks=1, chunked_params=False):
    """True-1F1B-memory pipelined forward+backward.

    The AD-through-the-tick-scan path (:func:`pipelined_apply`) stores one
    residual per tick — O(M + L) activations per device. The reference's
    1F1B exists precisely to avoid that
    (``reference:apex/transformer/pipeline_parallel/schedules/
    fwd_bwd_pipelining_without_interleaving.py:155-345`` holds at most
    O(pp) microbatches in flight; ``free_output_tensor``,
    ``common.py:198-249``, frees each output the moment its consumer is
    done). This driver reproduces that bound the SPMD way: ONE scan whose
    tick does one forward microbatch AND one backward microbatch per
    global stage, with the backward built from an explicit ``jax.vjp``
    that *recomputes* the stage forward (the reference's
    activation-checkpoint + free trade). The scan itself is never
    differentiated, so its carry — not AD residuals — is the whole
    activation memory:

    - ``saved``: per-chunk input-activation rings of ``2(L - c*S)`` slots
      (chunk c's in-flight window; at global stage g only ``2(L-g)-1``
      are live),
    - one in-transit activation + one in-transit cotangent per chunk,
    - the fp32 grad accumulators.

    With ``num_chunks`` = V > 1 this is the interleaved virtual pipeline
    (Megatron layout: chunk c on device d is global stage ``g = c*S + d``,
    L = S*V global stages,
    ``reference:.../fwd_bwd_pipelining_with_interleaving.py:25-375``);
    V = 1 reduces to plain 1F1B. Microbatch m runs forward at global
    stage g at tick ``m + g`` and backward at tick ``m + 2L - 1 - g``;
    total ticks ``M + 2L - 1``. The cotangent for (m, g) arrives from
    stage g+1's ``dx`` of the previous tick via the reverse rotation
    (wrapping from device 0 chunk c+1 back to device S-1 chunk c — the
    mirror of the forward wrap); the last global stage seeds from the
    loss vjp. Bubble ticks carry exactly-zero cotangents (vjp is linear
    in the seed), so no masking of the grad accumulation is needed beyond
    the loss/seed masks.

    Slot-reuse safety: a forward write at m_f can only collide with a
    pending backward read at m_b if the (even) chunk ring size divides
    m_f - m_b = 2L - 1 - 2g, which is odd — impossible; and the ring
    covers the window since 2(L - c*S) >= 2L - 2g for every device.

    Compiled temp memory is O(1) in M — asserted by
    ``tests/test_pipeline_memory.py``.
    """
    if embed_fn is not None and shared_params is None:
        raise ValueError(
            "embed_fn takes (shared_params, microbatch); pass the embedding "
            "parameters via shared_params so they are differentiated")
    S = _axis_size(PIPE_AXIS)
    rank = jax.lax.axis_index(PIPE_AXIS)
    M = jax.tree_util.tree_leaves(microbatches)[0].shape[0]
    V = num_chunks
    L = S * V
    T = M + 2 * L - 1
    _record_schedule_metrics(M, T, M)
    # per-chunk saved-activation window: chunk c's global stages start at
    # c*S, so at most 2(L - c*S) - 1 microbatches are in flight there; an
    # EVEN buffer size keeps the odd-difference collision-safety argument
    # (below) while not over-allocating the uniform 2L for every chunk
    B = [2 * (L - c * S) for c in range(V)]
    # chunked_params: caller passes leaves with a leading (num_chunks, ...)
    # axis (the interleaved API, valid even at num_chunks=1); otherwise raw
    stacked = chunked_params
    p_stack = params if stacked else jax.tree_util.tree_map(
        lambda p: p[None], params)

    def chunk_params(c):
        return jax.tree_util.tree_map(
            lambda p: jax.lax.index_in_dim(p, c, 0, keepdims=False), p_stack)

    f = RematPolicy.resolve(remat).wrap(stage_fn)

    def mb_at(m):
        return jax.tree_util.tree_map(
            lambda v: jax.lax.dynamic_index_in_dim(
                v, jnp.clip(m, 0, M - 1), 0, keepdims=False), microbatches)

    # activation shape/dtype (after embed, if any)
    if embed_fn is None:
        if not isinstance(microbatches, jnp.ndarray):
            raise ValueError("pytree microbatches require embed_fn")
        act_shape, act_dtype = microbatches.shape[1:], microbatches.dtype
    else:
        act_aval = jax.eval_shape(
            lambda sh, mb: embed_fn(sh, mb), shared_params, mb_at(0))
        act_shape, act_dtype = act_aval.shape, act_aval.dtype

    def first_stage_input(shared, mb):
        if embed_fn is not None:
            return embed_fn(shared, mb).astype(act_dtype)
        return mb.astype(act_dtype)

    def stage_and_loss(p, shared, xb, mb, m, c):
        """Uniform composite for chunk ``c``: global stage 0 re-derives its
        input from the microbatch (so embed params are differentiated),
        other stages use the saved input; the loss head runs only on the
        last local chunk (static) and is seeded only on the last device.
        ``mb``/``xb`` must already be chained into the tick's collective
        order (see the barriers in ``tick``)."""
        if c == 0:
            x_in = jnp.where(rank == 0, first_stage_input(shared, mb), xb)
        else:
            x_in = xb
        y = f(p, x_in, c * S + rank)
        if c == V - 1:
            l = loss_fn(y, m) if shared_params is None \
                else loss_fn(shared, y, m)
        else:
            l = jnp.zeros((), jnp.float32)
        return y.astype(act_dtype), l

    f32 = jnp.float32

    def tick(carry, t):
        act_bufs, cot_bufs, saved, acc_g, acc_sg, loss_sum = carry
        # collective-ordering note: the forward rotation, each chunk's
        # stage apply / vjp psums, and the backward rotation are mutually
        # data-independent, and XLA's CPU thunk runtime may run
        # independent collectives concurrently per device — with devices
        # arriving in different orders the rendezvous can cross-match and
        # hit the 40s abort. optimization_barriers thread every chunk's
        # work into one global order. (On TPU the static schedule makes
        # them no-ops.)
        chain = None

        # ---- forward sub-tick: one microbatch enters each global stage
        outs = []
        for c in range(V):
            m_f = t - (c * S + rank)
            x = jax.lax.index_in_dim(act_bufs, c, 0, keepdims=False)
            if chain is not None:
                x, _ = jax.lax.optimization_barrier((x, chain))
            if c == 0:
                # the embed's collectives depend only on loop-invariants;
                # chain the microbatch slice behind the carried activation
                mb_f, x = jax.lax.optimization_barrier((mb_at(m_f), x))
                x = jnp.where(rank == 0,
                              first_stage_input(shared_params, mb_f), x)
            y = f(chunk_params(c), x, c * S + rank)
            saved = (saved[:c]
                     + (saved[c].at[jnp.mod(m_f, B[c])].set(x),)
                     + saved[c + 1:])
            outs.append(y.astype(act_dtype))
            chain = outs[-1]
        received = rotate_forward(jnp.stack(outs))
        new_act = [received[0]]
        for c in range(1, V):
            # wrap: device 0's chunk c consumes last device's chunk c-1
            new_act.append(jnp.where(rank == 0, received[c - 1],
                                     received[c]))
        act_next = jnp.stack(new_act)
        chain, saved = jax.lax.optimization_barrier((act_next, saved))

        # ---- backward sub-tick: one microbatch leaves each global stage
        dxs = []
        for c in range(V):
            g = c * S + rank
            m_b = t - 2 * L + 1 + g
            valid_b = jnp.logical_and(m_b >= 0, m_b < M)
            xb = saved[c][jnp.mod(m_b, B[c])]
            xb, _ = jax.lax.optimization_barrier((xb, chain))
            xb, mb_b = jax.lax.optimization_barrier((xb, mb_at(m_b)))
            (y_b, l_b), vjp_fn = jax.vjp(
                lambda p, sh, x: stage_and_loss(p, sh, x, mb_b, m_b, c),
                chunk_params(c), shared_params, xb)
            dy = jax.lax.index_in_dim(cot_bufs, c, 0, keepdims=False)
            if c == V - 1:
                # global stage L-1 seeds from the loss, not the rotation
                dy = jnp.where(rank == S - 1, jnp.zeros_like(dy), dy)
                dl = jnp.where(
                    jnp.logical_and(rank == S - 1, valid_b),
                    jnp.asarray(grad_scale, f32) / M, jnp.asarray(0.0, f32))
                loss_sum = loss_sum + jnp.where(
                    jnp.logical_and(rank == S - 1, valid_b),
                    l_b.astype(f32), 0.0)
            else:
                dl = jnp.asarray(0.0, f32)
            # seed types must match the primal outputs' varying axes
            # exactly (e.g. data-varying under the DDP pattern)
            dy = cast_to_vma(dy.astype(y_b.dtype), _leaf_vma(y_b))
            dl = cast_to_vma(dl.astype(l_b.dtype), _leaf_vma(l_b))
            dparams, dshared, dxb = vjp_fn((dy, dl))
            acc_g = jax.tree_util.tree_map(
                lambda a, dg: a.at[c].add(dg.astype(f32)), acc_g, dparams)
            if shared_params is not None:
                acc_sg = jax.tree_util.tree_map(
                    lambda a, dg: a + dg.astype(f32), acc_sg, dshared)
            dxs.append(dxb.astype(act_dtype))
            chain = dxs[-1]
        recv_d = rotate_backward(jnp.stack(dxs))
        new_cot = []
        for c in range(V):
            if c < V - 1:
                # wrap mirror: device S-1's chunk c consumes device 0's
                # chunk c+1 (global stage g+1 = (c+1)*S)
                new_cot.append(jnp.where(rank == S - 1, recv_d[c + 1],
                                         recv_d[c]))
            else:
                new_cot.append(recv_d[c])  # rank S-1 re-seeded above
        cot_next = jnp.stack(new_cot)
        # close the chain: the next tick's forward rotation must not start
        # until this tick's backward rotation is issued
        act_next, cot_next = jax.lax.optimization_barrier(
            (act_next, cot_next))

        return (act_next, cot_next, saved, acc_g, acc_sg, loss_sum), None

    zeros_g = jax.tree_util.tree_map(
        lambda p: jnp.zeros(jnp.shape(p), f32), p_stack)
    zeros_sg = (None if shared_params is None else jax.tree_util.tree_map(
        lambda p: jnp.zeros(jnp.shape(p), f32), shared_params))
    init = (jnp.zeros((V,) + act_shape, act_dtype),
            jnp.zeros((V,) + act_shape, act_dtype),
            tuple(jnp.zeros((B[c],) + act_shape, act_dtype)
                  for c in range(V)),
            zeros_g, zeros_sg, jnp.asarray(0.0, f32))

    # fixed-point each carry leaf's varying-axes set (the stage body may
    # add axes, e.g. TP makes activations tensor-varying, while LN grad
    # accumulators must stay tensor-replicated)
    vma_tree = _fixed_point_vma(tick, init, jnp.asarray(0))

    def tick_stable(carry, t):
        new_carry, _ = tick(carry, t)
        return jax.tree_util.tree_map(cast_to_vma, new_carry, vma_tree), None

    (
        _, _, _, acc_g, acc_sg, loss_sum
    ), _ = jax.lax.scan(
        tick_stable, jax.tree_util.tree_map(cast_to_vma, init, vma_tree),
        jnp.arange(T))

    mean_loss = jax.lax.psum(
        jnp.where(rank == S - 1, loss_sum / M, 0.0), PIPE_AXIS)
    inv_scale = 1.0 / jnp.asarray(grad_scale, f32)
    stage_grads = jax.tree_util.tree_map(lambda g: g * inv_scale, acc_g)
    if not stacked:
        stage_grads = jax.tree_util.tree_map(lambda g: g[0], stage_grads)
    if shared_params is None:
        return mean_loss, stage_grads

    # shared_params enter pipe-INVARIANT, so the vjp's type reconciliation
    # already psums their per-tick cotangent across stages — every rank
    # accumulates the replicated total. If a carry cast left the
    # accumulator pipe-varying-TYPED, psum/S restores the invariant type
    # without double counting the S identical copies.
    def _finalize_shared(g):
        g = g * inv_scale
        if PIPE_AXIS in _leaf_vma(g):
            g = jax.lax.psum(g, PIPE_AXIS) / S
        return g

    shared_grads = jax.tree_util.tree_map(_finalize_shared, acc_sg)
    return mean_loss, (stage_grads, shared_grads)


# ---------------------------------------------------------------------------
# pipelined schedules (loss + grads)
# ---------------------------------------------------------------------------

def _pipelined_fwd_bwd(stage_fn, loss_fn, stage_params, microbatches,
                       num_chunks, forward_only, remat, grad_scale,
                       shared_params=None, embed_fn=None):
    """Shared driver: loss = mean over microbatches of
    ``loss_fn(final_stage_output, m)``, computed at the last stage and
    psum-shared over ``pipe``; grads via AD through the scan.

    ``shared_params`` (optional) are pipe-replicated parameters consumed by
    ``embed_fn(shared, microbatch)`` on global stage 0 and by
    ``loss_fn(shared, y, m)`` on the last stage — the pipelined embedding +
    tied output head. Because shared params enter ``shard_map`` replicated
    (device-invariant type), AD itself inserts the cross-stage psum that
    makes their cotangent invariant again — the reference's embedding-group
    allreduce (first + last stage contributions,
    ``reference:apex/transformer/parallel_state.py:215-247``,
    ``schedules/common.py:29-148`` pre/post_process) falls out of the VMA
    type system rather than being an explicit collective here (verified
    against a single-device reference in
    ``tests/test_transformer_parallel.py::test_gpt_pipelined_embedding_and_tied_head``).
    """
    if embed_fn is not None and shared_params is None:
        raise ValueError(
            "embed_fn takes (shared_params, microbatch); pass the embedding "
            "parameters via shared_params so they are differentiated")
    m = jax.tree_util.tree_leaves(microbatches)[0].shape[0]

    def total_loss(params):
        # pipelined_apply already broadcasts the final stage's outputs over
        # the pipe axis, so the loss is replicated by construction
        if shared_params is None:
            outs = pipelined_apply(stage_fn, params, microbatches,
                                   num_chunks=num_chunks, remat=remat)
            losses = jax.vmap(loss_fn)(outs, jnp.arange(m))
        else:
            stages, shared = params
            ef = (lambda mb: embed_fn(shared, mb)) \
                if embed_fn is not None else None
            outs = pipelined_apply(stage_fn, stages, microbatches,
                                   num_chunks=num_chunks, remat=remat,
                                   embed_fn=ef)
            losses = jax.vmap(lambda y, i: loss_fn(shared, y, i))(
                outs, jnp.arange(m))
            # the head runs "for real" only on the last stage (the broadcast
            # outs make every rank compute an identical copy): masking the
            # loss here (a) matches the reference's loss-on-last-stage and
            # (b) routes the head's shared-param cotangent to rank S-1 only,
            # so the psum below counts it exactly once
            rank = jax.lax.axis_index(PIPE_AXIS)
            S = _axis_size(PIPE_AXIS)
            total = jnp.mean(losses)
            return jax.lax.psum(
                jnp.where(rank == S - 1, total, jnp.zeros_like(total)),
                PIPE_AXIS)
        return jnp.mean(losses)

    diff_params = stage_params if shared_params is None \
        else (stage_params, shared_params)
    if forward_only:
        return total_loss(diff_params), None
    loss, grads = jax.value_and_grad(
        lambda p: total_loss(p) * grad_scale)(diff_params)
    grads = jax.tree_util.tree_map(
        lambda g: (g / grad_scale).astype(jnp.float32), grads)
    return loss / grad_scale, grads


def forward_backward_pipelining_without_interleaving(
    forward_step_func: Callable,
    batch: jnp.ndarray,
    params: Any,
    *,
    loss_fn: Callable,
    forward_only: bool = False,
    remat: Any = False,
    grad_scale: Any = 1.0,
    shared_params: Any = None,
    embed_fn: Optional[Callable] = None,
    memory_efficient: bool = True,
):
    """Pipelined schedule matching 1F1B
    (``fwd_bwd_pipelining_without_interleaving.py:155-345``) in output AND —
    by default — in its O(pp) activation-memory bound (see
    :func:`_onef1b_fwd_bwd`).

    ``forward_step_func(stage_params, x, stage_index) -> y`` is the uniform
    stage body; ``loss_fn(final_output, microbatch_index) -> scalar``.
    ``params`` leaves must NOT carry a chunk axis (single chunk per stage).
    Returns ``(mean_loss, grads)`` — grads for this device's stage params.

    With ``shared_params``/``embed_fn`` (pipelined embedding + tied head, see
    ``_pipelined_fwd_bwd``), ``loss_fn(shared, y, m)`` and grads are
    ``(stage_grads, shared_grads)`` with shared_grads psummed over ``pipe``.

    ``memory_efficient=False`` selects the AD-through-the-tick-scan driver
    (O(M + pp) per-tick residuals; cheaper per step at small M since the
    forward is not recomputed).

    ``remat`` accepts the legacy bool (True == "full"), a mode string, or
    a :class:`~apex_tpu.remat.RematPolicy` — "selective"/"offload" keep
    the registry-tagged activations the stage emits resident/offloaded
    instead of recomputing everything (see ``apex_tpu/remat.py``).
    """
    if memory_efficient and not forward_only:
        return _onef1b_fwd_bwd(
            forward_step_func, loss_fn, params, batch, remat, grad_scale,
            shared_params=shared_params, embed_fn=embed_fn)
    chunked = jax.tree_util.tree_map(lambda p: p[None], params)
    loss, grads = _pipelined_fwd_bwd(
        forward_step_func, loss_fn, chunked, batch, 1, forward_only, remat,
        grad_scale, shared_params=shared_params, embed_fn=embed_fn)
    if grads is not None:
        stage_grads = grads[0] if shared_params is not None else grads
        stage_grads = jax.tree_util.tree_map(lambda g: g[0], stage_grads)
        grads = (stage_grads, grads[1]) if shared_params is not None \
            else stage_grads
    return loss, grads


def forward_backward_pipelining_with_interleaving(
    forward_step_func: Callable,
    batch: jnp.ndarray,
    params: Any,
    *,
    loss_fn: Callable,
    num_model_chunks: int,
    forward_only: bool = False,
    remat: Any = False,
    grad_scale: Any = 1.0,
    shared_params: Any = None,
    embed_fn: Optional[Callable] = None,
    memory_efficient: bool = True,
):
    """Interleaved virtual-pipeline schedule
    (``fwd_bwd_pipelining_with_interleaving.py:25-375``): each device holds
    ``num_model_chunks`` stage chunks, Megatron layout (chunk c on device d =
    global stage ``c*S+d``). ``params`` leaves carry a leading
    ``(num_model_chunks, ...)`` axis.

    ``memory_efficient=True`` (default) runs the vjp-driven 1F1B driver
    with O(L)-in-flight activation memory (see :func:`_onef1b_fwd_bwd`);
    ``False`` selects the AD-through-the-tick-scan driver."""
    if memory_efficient and not forward_only:
        return _onef1b_fwd_bwd(
            forward_step_func, loss_fn, params, batch, remat, grad_scale,
            shared_params=shared_params, embed_fn=embed_fn,
            num_chunks=num_model_chunks, chunked_params=True)
    return _pipelined_fwd_bwd(
        forward_step_func, loss_fn, params, batch, num_model_chunks,
        forward_only, remat, grad_scale, shared_params=shared_params,
        embed_fn=embed_fn)


def get_forward_backward_func(virtual_pipeline_model_parallel_size: Optional[int],
                              pipeline_model_parallel_size: int):
    """Dispatch (``schedules/__init__.py:22``)."""
    if pipeline_model_parallel_size > 1:
        if virtual_pipeline_model_parallel_size is not None:
            return forward_backward_pipelining_with_interleaving
        return forward_backward_pipelining_without_interleaving
    return forward_backward_no_pipelining

"""Flash attention — Pallas TPU kernel family.

TPU replacement for the reference's two fused-attention stacks:
``reference:apex/contrib/csrc/fmha/`` (FlashAttention-style fixed-seqlen
kernels, fp16, seqlen<=512) and
``reference:apex/contrib/csrc/multihead_attn/`` (fused QKV/softmax/AV with
mask + optional residual+LN epilogues, seqlen<=2048 via the Megatron softmax).
One blockwise-online-softmax kernel subsumes both with no seqlen cap: scores
never materialize in HBM, so memory is O(sq·d) instead of O(sq·sk).

Forward: grid ``(b*h, sq/block_q, sk/block_k)`` with the kv dimension
innermost; running ``(m, l, acc)`` live in VMEM scratch across kv steps
(TPU grid execution is sequential per core, the canonical Pallas flash
pattern), ``m`` and ``l`` the same in all 128 lanes of a row. Backward
recomputes probabilities from the saved per-row logsumexp (same
recompute-not-store trade as the CUDA dgrad kernels) in ONE kernel gridded
``(b*h, sk/block_k, sq/block_q)``: dk and dv gather a kv tile at a time, dq
of the whole head in VMEM. Both kernels walk a grid tile in sub-tiles and
do for each only what its place under the causal band needs (see "the
schedule" below). Rows that are fully masked out save ``lse = +inf`` so the
backward's ``p = exp(s - lse)`` underflows to exactly zero instead of
producing ``exp(-inf - -inf) = 1`` garbage (ADVICE r1).

``bias`` is an additive score bias (the general form of the reference's
padding masks — additive -10000 fills, ``scaled_masked_softmax.h``). It is
kept in its broadcastable shape end to end: broadcast dims map to block
index 0 in the BlockSpec and broadcasting happens in VMEM, so a padding
mask ``(b, 1, 1, sk)`` costs O(b·sk) HBM, not O(b·h·sq·sk).

``bias`` gradients: **zero by default** — differentiating through ``bias``
without passing ``bias_requires_grad=True`` silently yields zeros (the
padding-mask case, where a gradient is meaningless). For *learned* biases
(ALiBi slopes, relative-position tables) pass ``bias_requires_grad=True``:
a dedicated kernel recomputes the score cotangent ds blockwise and
accumulates its sum over the broadcast dims directly into a bias-shaped
output — dbias costs O(|bias|) HBM, never the full score matrix.

Dropout runs *inside* the kernel (the ``philox.cuh`` path of
fast_multihead_attn / ``dropout.cuh:272``): a counter-based hash RNG keyed
on ``(seed, batch·head, global row, global col)`` generates the keep mask
blockwise, so the backward regenerates the identical mask from the same
counters with no mask storage — the Philox design, in backend-portable
uint32 ops (``pltpu.prng_*`` has no CPU interpret path). Masks are applied
to the normalized probabilities (scaled 1/(1-rate)); the softmax normalizer
uses the undropped probabilities, matching the reference kernels.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.backend import pallas_interpret as _interp

__all__ = ["flash_attention", "mha_reference", "supports_flash",
           "dropout_keep_mask", "paged_decode_attention",
           "paged_work_list"]

NEG_INF = -1e30

# murmur3 finalizer constants — numpy scalars embed as immediates in the
# kernel jaxpr (jnp scalars would be captured consts, which Pallas rejects)
_MIX1 = np.uint32(0x85EBCA6B)
_MIX2 = np.uint32(0xC2B2AE35)
_GOLD = np.uint32(0x9E3779B1)


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * _MIX1
    x = x ^ (x >> 13)
    x = x * _MIX2
    return x ^ (x >> 16)


def _pack_seed(dropout_seed) -> jnp.ndarray:
    """Full 32-bit seed as two fp32-exact 16-bit halves ``[hi, lo]`` —
    fp32 is the SMEM/custom_vjp-friendly carrier but only represents ints to
    2**24, so the seed rides split (each half < 2**16 is exact)."""
    s = jnp.asarray(dropout_seed).astype(jnp.int32)
    hi = jax.lax.shift_right_logical(s, 16) & 0xFFFF
    lo = s & 0xFFFF
    return jnp.stack([hi, lo]).astype(jnp.float32).reshape(2)


def _unpack_seed(hi_f, lo_f):
    # f32 -> i32 -> u32: Mosaic has no direct float->unsigned cast
    hi = hi_f.astype(jnp.int32)
    lo = lo_f.astype(jnp.int32)
    return (jax.lax.shift_left(hi, 16) | lo).astype(jnp.uint32)


def _keep_mask(seed2, bh, row0, col0, shape, rate):
    """Counter-based dropout keep mask for the ``shape`` score tile whose
    first entry is global ``(row0, col0)`` of batch-head ``bh`` — the
    ``philox.cuh`` analog. ``seed2`` is the ``(hi, lo)`` fp32 pair from
    :func:`_pack_seed`. Depends only on the *global* (seed, bh, row, col)
    coordinates, so every kernel (fwd, dq, dkv, dbias), whatever tile or
    sub-tile it walks, and the host-side test reference regenerate the
    identical mask."""
    seed = _unpack_seed(seed2[0], seed2[1])
    row = (row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
           ).astype(jnp.uint32)
    col = (col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
           ).astype(jnp.uint32)
    h = _mix32(seed ^ _mix32(jnp.asarray(bh).astype(jnp.uint32)))
    # two finalizer rounds over the combined counter (single-round murmur
    # finalizers show detectable structure; a second round is cheap)
    x = _mix32(_mix32(h ^ _mix32(row * _GOLD + col)) + _GOLD)
    # compare in the integer domain (Mosaic has no unsigned->float cast):
    # keep iff the top-24-bit draw >= rate * 2^24
    thresh = np.int32(int(rate * (1 << 24)))
    return (x >> np.uint32(8)).astype(jnp.int32) >= thresh


def dropout_keep_mask(seed, b, h, sq, sk, rate):
    """Host/XLA version of the in-kernel dropout mask (for parity tests and
    the non-Pallas fallback): (b, h, sq, sk) boolean keep mask identical to
    what the kernels generate for ``seed``."""
    seed2 = _pack_seed(seed)
    bh_ids = jnp.arange(b * h, dtype=jnp.int32)
    masks = jax.vmap(
        lambda bh: _keep_mask((seed2[0], seed2[1]), bh, 0, 0, (sq, sk), rate))(
            bh_ids)
    return masks.reshape(b, h, sq, sk)


def supports_flash(sq: int, sk: int, d: int, block_q: int, block_k: int) -> bool:
    """Eligibility for the Pallas path (cf. the reference's per-kernel seqlen
    gates, ``fused_softmax.py:159-179`` / ``setup.py:544-560`` — here the gate
    is only tile alignment, not a seqlen cap).

    Decode shapes (``sq == 1`` against a cached ``sk``) are eligible too:
    a single query row rides one padded sublane tile (``block_q == 1``), so
    only the key-side tiling gates. Callers historically assumed
    ``sq == sk`` — the KV-cached decode path is the second caller family.
    """
    if sq == 1:
        return (sk % block_k == 0 and d % 8 == 0 and block_k % 128 == 0
                and block_q == 1)
    return (sq % block_q == 0 and sk % block_k == 0 and d % 8 == 0
            and block_q % 8 == 0 and block_k % 128 == 0)


def _norm_segment_ids(segment_ids, sq, sk):
    """Accept ``ids (b, s)`` (self-attention) or ``(q_ids, kv_ids)``."""
    if isinstance(segment_ids, (tuple, list)):
        q_ids, kv_ids = segment_ids
    else:
        if sq != sk:
            raise ValueError(
                "cross-attention needs segment_ids=(q_ids, kv_ids)")
        q_ids = kv_ids = segment_ids
    q_ids = jnp.asarray(q_ids)
    kv_ids = jnp.asarray(kv_ids)
    if q_ids.shape[-1] != sq or kv_ids.shape[-1] != sk:
        raise ValueError(
            f"segment id lengths {q_ids.shape[-1]}/{kv_ids.shape[-1]} do "
            f"not match sequence lengths {sq}/{sk}")
    return q_ids, kv_ids


def mha_reference(q, k, v, bias=None, causal=False,
                  softmax_scale: Optional[float] = None,
                  dropout_rate: float = 0.0, dropout_seed=None,
                  segment_ids=None, kv_length=None,
                  window: Optional[int] = None):
    """Plain-XLA attention; the parity reference for the kernel (the role of
    the Python attention in ``reference:apex/contrib/test/fmha/test_fmha.py``).
    With ``dropout_rate > 0`` it applies the *same* counter-based mask as the
    Pallas kernels, so fallback and kernel paths agree bitwise in expectation
    and exactly for a given seed.

    ``kv_length``: the KV-cache oracle path — an int array ``(b,)`` giving
    the number of VALID cache entries per batch row; key positions at or
    beyond it are masked out (the ground truth for
    :func:`paged_decode_attention`, whose pool blocks carry garbage past
    the write cursor). Rows with
    length 0 produce an exactly-zero output, matching the kernel.

    ``window`` (with ``causal``): row ``i`` reads column ``j`` only while
    ``0 <= i - j < window``, the row's own position counted. ``k``/``v``
    may carry fewer heads than ``q`` (grouped-query attention: query head
    ``i`` reads KV head ``i // (h // h_kv)``); the oracle repeats them."""
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(q.shape[-1])
    if k.shape[1] != q.shape[1]:
        group = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * softmax_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if kv_length is not None:
        lengths = jnp.asarray(kv_length).astype(jnp.int32)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, k.shape[2]), 3)
        s = jnp.where(col < lengths[:, None, None, None], s, NEG_INF)
    if segment_ids is not None:
        q_ids, kv_ids = _norm_segment_ids(segment_ids, q.shape[2], k.shape[2])
        s = jnp.where((q_ids[:, None, :, None] == kv_ids[:, None, None, :]),
                      s, NEG_INF)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where(col > row + (sk - sq), NEG_INF, s)
        if window is not None:
            s = jnp.where(col <= row + (sk - sq) - window, NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.max(s, axis=-1, keepdims=True) <= NEG_INF, 0.0, p)
    if dropout_rate > 0.0:
        b, h, sq, sk = p.shape
        keep = dropout_keep_mask(dropout_seed, b, h, sq, sk, dropout_rate)
        p = jnp.where(keep, p, 0.0) / (1.0 - dropout_rate)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


# ---------------------------------------------------------------------------
# the schedule: which sub-tiles of a grid tile run, and which are masked
# ---------------------------------------------------------------------------
#
# A grid tile (block_q x block_k) is walked in sub-tiles (sub_q x sub_k). A
# causal sub-tile is one of three kinds, told from where it lies: wholly
# above the diagonal (or left of the window) - never computed, and never
# fetched where that holds of the whole grid tile; wholly visible -
# computed with no mask work at all; crossed by an edge - masked, and
# where it is a square ON the diagonal its rows are walked in strips, each
# against only the columns it can see. ``_key_spans`` gives the kinds as
# ranges of sub-tile indices; it takes python ints (``flash_tile_plan``,
# the tests) and traced scalars (the kernels) alike.
#
# What the v5e taught about sizes (my chip runs, PR 33; PERF.md section 6):
# a sub-tile's chain - product, row max, exp, row sum, product - is one
# dependent chain, and the compiler overlaps nothing across the steps of a
# loop, so a 128 x 128 sub-tile costs what its latencies add up to (0.54 us,
# 51 cycles a score vreg) and a 512 x 512 one is bound by the MXU (head dim
# 64 half-fills it in every product). Sub-tiles are therefore LARGE, and
# the diagonal's dead half is cut inside one straight-line body, not by
# smaller sub-tiles.

def _clamp(x, lo, hi):
    if isinstance(x, int) and isinstance(lo, int):
        return max(lo, min(x, hi))
    return jnp.clip(x, lo, hi)


def _key_spans(row0, sub_q, col_base, n_c, sub_k, offset, causal, window):
    """For the rows ``[row0, row0 + sub_q)`` and the ``n_c`` key sub-tiles
    from column ``col_base``: ``(lo, a, b, hi)`` - sub-tiles ``[lo, a)`` are
    crossed by the window's left edge, ``[a, b)`` are wholly visible,
    ``[b, hi)`` are crossed by the diagonal, the rest never run."""
    if not causal:
        return 0, 0, n_c, n_c
    vis = row0 + offset + 1 - col_base      # the first row sees [.., vis)
    b = _clamp(vis // sub_k, 0, n_c)
    hi = _clamp((vis + sub_q - 1 + sub_k - 1) // sub_k, 0, n_c)
    if window is None:
        return 0, 0, b, hi
    lo = _clamp((vis - window) // sub_k, 0, n_c)
    a = _clamp((vis + sub_q - 1 - window + sub_k - 1) // sub_k, lo, hi)
    return lo, a, _clamp(b, a, hi), hi


def flash_tile_plan(sq, sk, block_q, block_k, sub_q, sub_k, causal=True,
                    window=None):
    """What the schedule does for one head: sub-tiles ``skipped``,
    ``unmasked`` and ``masked``, and ``scores_computed`` against
    ``scores_needed``. The kernels walk exactly these ranges
    (:func:`_key_spans`, :func:`_strips`); a pure function of the static
    shapes."""
    offset, n_c = sk - sq, block_k // sub_k
    plan = dict(skipped=0, unmasked=0, masked=0)
    for row0 in range(0, sq, sub_q):
        for col_base in range(0, sk, block_k):
            lo, a, b, hi = _key_spans(row0, sub_q, col_base, n_c, sub_k,
                                      offset, causal, window)
            plan["unmasked"] += b - a
            plan["masked"] += (a - lo) + (hi - b)
            plan["skipped"] += n_c - (hi - lo)
    diagonal = _on_diagonal((block_q, block_k, sub_q, sub_k), offset, causal,
                            window, True)
    plan["scores_computed"] = plan["unmasked"] * sub_q * sub_k + sum(
        (rs.stop - rs.start) * ks.stop
        for rs, ks in _strips(sub_q, sub_k, diagonal)) * plan["masked"]
    rows = np.arange(sq) + offset + 1       # columns [.., rows) are seen
    seen = np.clip(rows, 0, sk) if causal else np.full(sq, sk)
    if window is not None:
        seen = seen - np.clip(rows - window, 0, sk)
    plan["scores_needed"] = int(seen.sum())
    return plan


def _span(lo, hi, n, body):
    """Run ``body(c)`` for the sub-tiles ``c`` in ``[lo, hi)`` of ``n``.
    Where there is one sub-tile the loop is a branch and ``c`` the python
    int 0; state lives in refs, so nothing is carried."""
    if isinstance(lo, int) and isinstance(hi, int) and hi - lo <= 1:
        if hi > lo:
            body(lo)
    elif n == 1:
        pl.when(hi > lo)(lambda: body(0))
    else:
        jax.lax.fori_loop(lo, hi, lambda c, _: body(c) or 0, 0)


def _sub(n, idx, size):
    """``(start, slice)`` of sub-tile ``idx`` of ``n`` along one dim of a
    block. Static where there is one: lane-dim slices (bias, segment ids)
    are only ever static."""
    if n == 1:
        start = 0
    elif isinstance(idx, int):
        start = idx * size
    else:
        start = pl.multiple_of(idx * size, size)
    return start, pl.ds(start, size)


def _folds(scale, dtype):
    """Whether the softmax scale goes into a ``(rows, d)`` operand instead
    of the score tile: only where that rounds nothing the score-side
    multiply would not (float32 operands, or a power of two)."""
    return dtype == jnp.float32 or math.frexp(scale)[0] == 0.5


def _scores(bias_ref, seed_ref, q_seg_ref, kv_seg_ref, bh, scale, offset,
            window, dropout_rate, s, rows, cols, row0, col0, masked):
    """``(scores, dead, keep)`` of the raw product ``s``, the score sub-tile
    at global ``(row0, col0)`` (block slices ``rows``, ``cols``): scaled
    (``scale`` None where it is folded into an operand), biased and masked
    - the one place the kernels build a mask; a kernel binds the arguments
    before ``s`` once. ``dead`` is None where
    nothing is masked: the causal / window part is ``col - row`` against
    one scalar, and only where ``masked`` says an edge crosses the
    sub-tile. Segment ids are the TPU-native form of the reference's varlen
    ``cu_seqlens`` packing
    (``reference:apex/contrib/csrc/fmha/fmha_api.cpp:420``). ``keep``: the
    dropout mask, None without dropout."""
    if scale is not None:
        s = s * scale
    if bias_ref is not None:   # (1|bq, bk) broadcasts over the block
        s = s + bias_ref[0, 0, rows if bias_ref.shape[2] > 1 else slice(None),
                         cols]
    dead = None
    if masked:
        diff = (jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                - jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
        edge = row0 + offset - col0
        dead = diff > edge
        if window is not None:
            dead = dead | (diff <= edge - window)
    if q_seg_ref is not None:
        apart = (q_seg_ref[0, 0, rows][:, None]
                 != kv_seg_ref[0, 0, cols][None, :])
        dead = apart if dead is None else dead | apart
    if dead is not None:
        s = jnp.where(dead, NEG_INF, s)
    keep = None
    if dropout_rate > 0.0:
        keep = _keep_mask((seed_ref[0], seed_ref[1]), bh, row0, col0, s.shape,
                          dropout_rate)
    return s, dead, keep


# Row strips a masked sub-tile ON the diagonal is walked in (my chip runs,
# PR 33: 2 beats 1 and 4, forward and backward: four strips are four short
# chains again)
_STRIPS = 2


def _on_diagonal(tile, offset, causal, window, plain):
    """Whether every masked sub-tile is a square ON the diagonal (its first
    row sees exactly its first column): then its rows are walked in strips,
    each against only the columns it can see (:func:`_strips`). ``plain``:
    no bias and no segment ids, whose blocks a strip would slice."""
    _, _, sub_q, sub_k = tile
    return bool(causal and window is None and plain and sub_q == sub_k
                and offset % sub_k == 0 and sub_q % (_STRIPS * _LANES) == 0)


def _strips(sub_q, sub_k, diagonal):
    """``(rows, cols)`` slices of a sub-tile to compute: all of it, or on
    the diagonal ``_STRIPS`` row strips with the columns at or under each."""
    if not diagonal:
        return [(slice(0, sub_q), slice(0, sub_k))]
    h = sub_q // _STRIPS
    return [(slice(t * h, (t + 1) * h), slice(0, (t + 1) * h))
            for t in range(_STRIPS)]


def _stack(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


_LANES = 128
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_NN = (((1,), (0,)), ((), ()))     # a @ b
_TN = (((0,), (0,)), ((), ()))     # a.T @ b


def _lanes(x, n):
    """A per-row statistic held the same in all 128 lanes, as ``n`` lanes.
    The running max and sum live that way: kept as ``(rows, 1)`` columns
    they cost a lane broadcast at every use and a masked store at every
    update, which was most of what a tile cost beside its products."""
    return jnp.tile(x, (1, -(-n // _LANES)))[:, :n]


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, q_seg_ref,
                kv_seg_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, tile, n_kv, offset,
                dropout_rate, window=None):
    bh, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    block_q, block_k, sub_q, sub_k = tile
    n_r, n_c = block_q // sub_q, block_k // sub_k
    fold = _folds(scale, q_ref.dtype)
    # a row can be wholly masked in a sub-tile before it has met a live
    # column only with segments, a window or sk < sq: m is still NEG_INF
    # there and exp(s - m) == 1 on the masked entries, zeroed explicitly
    rezero = q_seg_ref is not None or window is not None or offset < 0
    diagonal = _on_diagonal(tile, offset, causal, window,
                            bias_ref is None and q_seg_ref is None)
    scores = functools.partial(
        _scores, bias_ref, seed_ref, q_seg_ref, kv_seg_ref, bh,
        None if fold else scale, offset, window, dropout_rate)

    @pl.when(j == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def row_body(r):
        (_, rows), row0 = _sub(n_r, r, sub_q), i * block_q + r * sub_q

        def sub_tile(c, masked):
            (_, cols), col0 = _sub(n_c, c, sub_k), j * block_k + c * sub_k
            q, k, v = q_ref[0, rows, :], k_ref[0, cols, :], v_ref[0, cols, :]
            if fold:
                q = q * scale
            m_prev, l_prev, acc = m_ref[rows, :], l_ref[rows, :], \
                acc_ref[rows, :]
            ms, ls, accs = [], [], []
            for rs, ks in _strips(sub_q, sub_k, masked and diagonal):
                s, dead, keep = scores(
                    jax.lax.dot_general(q[rs], k[ks], _NT,
                                        preferred_element_type=jnp.float32),
                    rows, cols, row0 + rs.start, col0, masked)
                m_new = jnp.maximum(m_prev[rs],
                                    jnp.max(s, axis=1, keepdims=True))
                p = jnp.exp(s - _lanes(m_new, s.shape[1]))
                if dead is not None and rezero:
                    p = jnp.where(dead, 0.0, p)
                corr = jnp.exp(m_prev[rs] - m_new)
                # softmax normalizer uses the UNdropped probabilities
                ls.append(l_prev[rs] * corr
                          + jnp.sum(p, axis=1, keepdims=True))
                ms.append(m_new)
                if keep is not None:
                    p = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_rate))
                accs.append(acc[rs] * _lanes(corr, v.shape[1])
                            + jax.lax.dot_general(
                                p.astype(v.dtype), v[ks], _NN,
                                preferred_element_type=jnp.float32))
            m_ref[rows, :], l_ref[rows, :] = _stack(ms), _stack(ls)
            acc_ref[rows, :] = _stack(accs)

        lo, a, b, hi = _key_spans(row0, sub_q, j * block_k, n_c, sub_k,
                                  offset, causal, window)
        _span(lo, a, n_c, lambda c: sub_tile(c, True))
        _span(a, b, n_c, lambda c: sub_tile(c, False))
        _span(b, hi, n_c, lambda c: sub_tile(c, True))

    _span(0, n_r, n_r, row_body)

    @pl.when(j == n_kv - 1)
    def _():
        l = l_ref[:]
        # fully-masked rows (l==0): 0 output, and lse=+inf so the backward's
        # exp(s - lse) underflows to 0 for every entry of the row
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / _lanes(safe_l, acc_ref.shape[1])
                    ).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l == 0.0, jnp.inf,
                               m_ref[:] + jnp.log(safe_l))[:, :1]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _p_ds(scores, dropout_rate, q, k, v, do, lse, delta, *where):
    """Shared backward recompute on one score sub-tile: ``p = exp(s - lse)``
    and ``ds = p * (dp_eff - delta)``; ``scores`` is :func:`_scores` bound
    to the kernel's side operands, ``where`` its ``(rows, cols, row0, col0,
    masked)``. A masked score is NEG_INF, so ``p``
    is exactly zero there whether the row's lse is finite or +inf (a fully
    masked row): no second select.

    With dropout the identical keep mask is regenerated from the counters:
    ``p_eff`` (for dv) is the dropped-and-rescaled probability, and
    ``dp_eff = keep ⊙ dp/(1-rate)`` feeds ds — the exact transpose of the
    forward's dropout-after-normalizer placement.
    """
    s, _, keep = scores(
        jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32),
        *where)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    p_eff = p
    if keep is not None:
        inv = 1.0 / (1.0 - dropout_rate)
        p_eff = jnp.where(keep, p, 0.0) * inv
        dp = jnp.where(keep, dp, 0.0) * inv
    return p_eff, p * (dp - delta)


def _bwd_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, q_seg_ref,
                kv_seg_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref,
                dv_ref, dq_acc, dk_acc, dv_acc, *, scale, causal, tile, n_q,
                n_kv, offset, dropout_rate):
    """dq, dk and dv in ONE pass over the scores: grid ``(bh, n_kv, n_q)``,
    q tiles innermost. dk and dv of kv tile ``j`` gather over its q tiles;
    dq of the WHOLE head gathers in ``dq_acc`` (its block index is fixed
    while the head runs, so it is written back once, after the head's last
    step). Five products and one exp a sub-tile where a dq kernel and a
    dk/dv kernel took seven and two."""
    bh = pl.program_id(0)
    j, i = pl.program_id(1), pl.program_id(2)
    block_q, block_k, sub_q, sub_k = tile
    n_r, n_c = block_q // sub_q, block_k // sub_k
    fold = _folds(scale, q_ref.dtype)

    @pl.when(jnp.logical_and(j == 0, i == 0))
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(i == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    diagonal = _on_diagonal(tile, offset, causal, None,
                            bias_ref is None and q_seg_ref is None)
    p_ds = functools.partial(
        _p_ds, functools.partial(
            _scores, bias_ref, seed_ref, q_seg_ref, kv_seg_ref, bh,
            None if fold else scale, offset, None, dropout_rate),
        dropout_rate)

    def row_body(r):
        (r0, rows), row0 = _sub(n_r, r, sub_q), i * block_q + r * sub_q

        def sub_tile(c, masked):
            (c0, cols), col0 = _sub(n_c, c, sub_k), j * block_k + c * sub_k
            q, do, k = q_ref[0, rows, :], do_ref[0, rows, :], k_ref[0, cols, :]
            v, lse, delta = v_ref[0, cols, :], lse_ref[0, rows, :], \
                delta_ref[0, rows, :]
            qs = q * scale if fold else q
            for rs, ks in _strips(sub_q, sub_k, masked and diagonal):
                high = rs.stop - rs.start
                p, ds = p_ds(qs[rs], k[ks], v[ks], do[rs], lse[rs],
                             delta[rs], rows, cols, row0 + rs.start, col0,
                             masked)
                ds = ds.astype(q.dtype)
                under = pl.ds(c0, ks.stop)
                dv_acc[under, :] += jax.lax.dot_general(
                    p.astype(do.dtype), do[rs], _TN,
                    preferred_element_type=jnp.float32)
                dk_acc[under, :] += jax.lax.dot_general(
                    ds, q[rs], _TN, preferred_element_type=jnp.float32)
                dq_acc[pl.ds(pl.multiple_of(
                    i * block_q + r0 + rs.start, high), high), :] += \
                    jax.lax.dot_general(ds, k[ks], _NN,
                                        preferred_element_type=jnp.float32)

        _, _, b, hi = _key_spans(row0, sub_q, j * block_k, n_c, sub_k,
                                 offset, causal, None)
        _span(0, b, n_c, lambda c: sub_tile(c, False))
        _span(b, hi, n_c, lambda c: sub_tile(c, True))

    _span(0, n_r, n_r, row_body)

    @pl.when(i == n_q - 1)
    def _():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    @pl.when(jnp.logical_and(j == n_kv - 1, i == n_q - 1))
    def _():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _dbias_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, q_seg_ref,
                  kv_seg_ref, do_ref, lse_ref,
                  delta_ref, db_ref, *, scale, causal, block_q, block_k,
                  swap, offset, dropout_rate, bh_fn):
    """Accumulate dbias = ds summed over the bias's broadcast dims.

    Grid is ``(kept_bh, a, b, r)`` with the reduced bh slices ``r``
    innermost (and, when the bias broadcasts over sq, the q-blocks too via
    ``swap``), so the output tile is revisited on consecutive steps and the
    reduction accumulates in VMEM — dbias costs O(|bias|) HBM, never the
    full (b·h, sq, sk) score matrix. One sub-tile a grid tile."""
    g, a, b_, r = (pl.program_id(n) for n in range(4))
    bh = bh_fn(g, r)  # program_id must be read at kernel top level, not
    # inside a pl.when branch (interpret mode cannot substitute it there)
    if swap:       # bias broadcast over sq: reduce over q-blocks as well
        j, i = a, b_
        first = jnp.logical_and(i == 0, r == 0)
    else:
        i, j = a, b_
        first = r == 0

    @pl.when(first)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)

    run = (j * block_k <= i * block_q + block_q - 1 + offset) if causal else True

    @pl.when(run)
    def _():
        _, ds = _p_ds(
            functools.partial(_scores, bias_ref, seed_ref, q_seg_ref,
                              kv_seg_ref, bh, scale, offset, None,
                              dropout_rate),
            dropout_rate, q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0],
            delta_ref[0], slice(None), slice(None), i * block_q, j * block_k,
            causal)
        if swap:
            db_ref[0, 0] += jnp.sum(ds, axis=0, keepdims=True)
        else:
            db_ref[0, 0] += ds


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-manual-axes of ``like`` — a
    pallas_call inside ``shard_map`` (check_vma) must declare how its
    outputs vary; they vary exactly like the q/k/v operands."""
    from apex_tpu.utils.vma import leaf_vma
    vma = leaf_vma(like)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _bias_spec(bias4, h, block_q, block_k, *, swapped):
    """BlockSpec for the 4D broadcastable bias ``(bb, hb, sqb, sk)`` where
    ``bb``/``hb``/``sqb`` are each 1 or full: broadcast dims map to block 0
    and the kernel broadcasts in VMEM (ADVICE r1: never materialize the
    full (b·h, sq, sk) bias in HBM)."""
    bb, hb, sqb, _ = bias4.shape
    bq = block_q if sqb > 1 else 1

    def imap_fwd(b, i, j):
        return (b // h if bb > 1 else 0, b % h if hb > 1 else 0,
                i if sqb > 1 else 0, j)

    def imap_swapped(b, j, i):
        return (b // h if bb > 1 else 0, b % h if hb > 1 else 0,
                i if sqb > 1 else 0, j)

    return pl.BlockSpec((1, 1, bq, block_k),
                        imap_swapped if swapped else imap_fwd,
                        memory_space=pltpu.VMEM)


def _seed_spec():
    """Dropout seed: a (2,) fp32 ``(hi, lo)`` pair in SMEM (see
    ``_pack_seed``), shared by every block."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _seg_specs(h, block_q, block_k, *, swapped):
    """Specs for packed-segment id arrays ``(b, 1, sq)`` / ``(b, 1, sk)``:
    one id row per *batch* (shared across heads), blocked along the
    sequence."""
    def q_map(b, a, c):
        i = c if swapped else a
        return (b // h, 0, i)

    def kv_map(b, a, c):
        j = a if swapped else c
        return (b // h, 0, j)

    return (pl.BlockSpec((1, 1, block_q), q_map, memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k), kv_map, memory_space=pltpu.VMEM))


def _live_kv(i, j, block_q, block_k, offset, n_kv, causal, window=None):
    """Index-map clamp of kv grid tile ``j`` to those q tile ``i`` can see:
    a tile above the diagonal or left of the window resolves to its
    neighbour, so its DMA is elided, not masked after the read."""
    if not causal:
        return j
    hi = (i * block_q + block_q - 1 + offset) // block_k
    jj = jnp.minimum(j, jnp.clip(hi, 0, n_kv - 1))
    if window is not None:
        lo = jnp.maximum(i * block_q + offset - window + 1, 0) // block_k
        jj = jnp.maximum(jj, lo)
    return jj


def _operands(q3, k3, v3, bias4, seed, segs, h, block_q, block_k, q_spec,
              kv_spec, *, dropout_rate, swapped):
    """``(in_specs, args, split)`` of what the forward and both backward
    kernels share: q, k, v and whichever of bias, dropout seed and segment
    ids the call has. ``split(refs)`` gives the seven refs the kernels
    name (None where absent) and the rest."""
    has_bias, has_seg = bias4 is not None, segs is not None
    has_drop = dropout_rate > 0.0
    in_specs, args = [q_spec, kv_spec, kv_spec], [q3, k3, v3]
    if has_bias:
        in_specs.append(_bias_spec(bias4, h, block_q, block_k,
                                   swapped=swapped))
        args.append(bias4)
    if has_drop:
        in_specs.append(_seed_spec())
        args.append(seed)
    if has_seg:
        in_specs += _seg_specs(h, block_q, block_k, swapped=swapped)
        args += list(segs)

    def split(refs):
        refs = list(refs)
        nxt = 3 + has_bias + has_drop
        return (refs[:3] + [refs[3] if has_bias else None,
                            refs[3 + has_bias] if has_drop else None,
                            refs[nxt] if has_seg else None,
                            refs[nxt + 1] if has_seg else None],
                refs[nxt + 2 * has_seg:])
    return in_specs, args, split


def _fwd_pallas(q3, k3, v3, bias4, seed, segs, h, *, scale, causal, tile,
                dropout_rate, window=None, kv_heads=None):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    block_q, block_k = tile[:2]
    n_q, n_kv = sq // block_q, sk // block_k

    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                          memory_space=pltpu.VMEM)
    # ``k3``/``v3`` hold ``kv_heads`` heads a sequence (the forward-only
    # serving form) and query head ``g`` reads KV head
    # ``g // (h // kv_heads)`` where it lies: no repeated copy
    kvh = h if kv_heads is None else kv_heads

    def kv_map(b, i, j):
        return ((b // h) * kvh + (b % h) // (h // kvh),
                _live_kv(i, j, block_q, block_k, sk - sq, n_kv, causal,
                         window), 0)
    kv_spec = pl.BlockSpec((1, block_k, d), kv_map,
                           memory_space=pltpu.VMEM)
    in_specs, args, split = _operands(
        q3, k3, v3, bias4, seed, segs, h, block_q, block_k, q_spec, kv_spec,
        dropout_rate=dropout_rate, swapped=False)

    def kernel(*refs):
        shared, rest = split(refs)
        _fwd_kernel(*shared, *rest, scale=scale, causal=causal, tile=tile,
                    n_kv=n_kv, offset=sk - sq, dropout_rate=dropout_rate,
                    window=window)

    named = {} if window is None and kv_heads is None \
        else {"name": "flash_attention_window"}
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_kv),
        in_specs=in_specs,
        out_specs=(q_spec,
                   pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0),
                                memory_space=pltpu.VMEM)),
        out_shape=(_sds((bh, sq, d), q3.dtype, q3),
                   _sds((bh, sq, 1), jnp.float32, q3)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32)],
        interpret=_interp(),
        **named,
    )(*args)
    return out, lse


def _bwd_pallas(q3, k3, v3, bias4, seed, segs, h, do3, lse, delta, *, scale,
                causal, tile, dropout_rate):
    """One kernel, grid ``(bh, n_kv, n_q)`` with the q tiles innermost: q
    tiles above the diagonal are neither computed nor fetched."""
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    offset = sk - sq
    block_q, block_k = tile[:2]
    n_q, n_kv = sq // block_q, sk // block_k

    def live_q(j, i):
        if not causal:
            return i
        return jnp.maximum(i, jnp.clip((j * block_k - offset) // block_q,
                                       0, n_q - 1))
    q_spec = pl.BlockSpec((1, block_q, d),
                          lambda b, j, i: (b, live_q(j, i), 0),
                          memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0),
                           memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, block_q, 1),
                            lambda b, j, i: (b, live_q(j, i), 0),
                            memory_space=pltpu.VMEM)
    head_spec = pl.BlockSpec((1, sq, d), lambda b, j, i: (b, 0, 0),
                             memory_space=pltpu.VMEM)
    in_specs, args, split = _operands(
        q3, k3, v3, bias4, seed, segs, h, block_q, block_k, q_spec, kv_spec,
        dropout_rate=dropout_rate, swapped=True)

    def kernel(*refs):
        shared, rest = split(refs)
        _bwd_kernel(*shared, *rest, scale=scale, causal=causal, tile=tile,
                    n_q=n_q, n_kv=n_kv, offset=offset,
                    dropout_rate=dropout_rate)

    # dq of the whole head is held in VMEM: its float32 gather and its
    # double-buffered output block; past a few MiB that needs saying
    held = sq * d * (4 + 2 * q3.dtype.itemsize)
    roomy = {} if held <= 4 << 20 else dict(
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=(24 << 20) + held))
    return pl.pallas_call(
        kernel,
        grid=(bh, n_kv, n_q),
        in_specs=in_specs + [q_spec, row_spec, row_spec],
        out_specs=(head_spec, kv_spec, kv_spec),
        out_shape=(_sds((bh, sq, d), q3.dtype, q3),
                   _sds((bh, sk, d), k3.dtype, k3),
                   _sds((bh, sk, d), v3.dtype, v3)),
        scratch_shapes=[pltpu.VMEM((sq, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=_interp(),
        **roomy,
    )(*args, do3, lse, delta)


def _dbias_pallas(q3, k3, v3, bias4, seed, segs, h, do3, lse, delta, *,
                  scale, causal, block_q, block_k, dropout_rate):
    """dbias via the accumulating kernel; HBM cost is O(|bias|)."""
    has_drop = dropout_rate > 0.0
    has_seg = segs is not None
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    n_q, n_kv = sq // block_q, sk // block_k
    bb, hb, sqb, _ = bias4.shape
    HB = bb * hb          # kept bh slices (one dbias tile-plane each)
    R = bh // HB          # bh slices reduced into each kept slice
    swap = sqb == 1       # bias broadcast over sq: reduce q-blocks too
    bq = block_q if not swap else 1

    def bh_of(g, r):
        if bb > 1 and hb > 1:
            return g
        if hb > 1:          # broadcast over batch: r enumerates b
            return r * hb + g
        if bb > 1:          # broadcast over heads: r enumerates h
            return g * h + r
        return r            # broadcast over both

    def kept(g):
        if bb > 1 and hb > 1:
            return (g // hb, g % hb)
        if hb > 1:
            return (0, g)
        if bb > 1:
            return (g, 0)
        return (0, 0)

    def ij(a, b_):
        return (b_, a) if swap else (a, b_)

    def q_map(g, a, b_, r):
        return (bh_of(g, r), ij(a, b_)[0], 0)

    def kv_map(g, a, b_, r):
        return (bh_of(g, r), ij(a, b_)[1], 0)

    def row_map(g, a, b_, r):
        return (bh_of(g, r), ij(a, b_)[0], 0)

    def bias_map(g, a, b_, r):
        bhv = bh_of(g, r)
        i, j = ij(a, b_)
        return (bhv // h if bb > 1 else 0, bhv % h if hb > 1 else 0,
                i if sqb > 1 else 0, j)

    def db_map(g, a, b_, r):
        i, j = ij(a, b_)
        return (*kept(g), i if sqb > 1 else 0, j)

    grid = (HB, n_kv, n_q, R) if swap else (HB, n_q, n_kv, R)
    q_spec = pl.BlockSpec((1, block_q, d), q_map, memory_space=pltpu.VMEM)
    kv_spec = pl.BlockSpec((1, block_k, d), kv_map, memory_space=pltpu.VMEM)
    row_spec = pl.BlockSpec((1, block_q, 1), row_map, memory_space=pltpu.VMEM)
    bias_spec = pl.BlockSpec((1, 1, bq, block_k), bias_map,
                             memory_space=pltpu.VMEM)
    db_spec = pl.BlockSpec((1, 1, bq, block_k), db_map,
                           memory_space=pltpu.VMEM)

    in_specs = [q_spec, kv_spec, kv_spec, bias_spec]
    args = [q3, k3, v3, bias4]
    if has_drop:
        in_specs.append(_seed_spec())
        args.append(seed)
    if has_seg:
        def qseg_map(g, a, b_, r):
            return (bh_of(g, r) // h, 0, ij(a, b_)[0])

        def kseg_map(g, a, b_, r):
            return (bh_of(g, r) // h, 0, ij(a, b_)[1])

        in_specs += [pl.BlockSpec((1, 1, block_q), qseg_map,
                                  memory_space=pltpu.VMEM),
                     pl.BlockSpec((1, 1, block_k), kseg_map,
                                  memory_space=pltpu.VMEM)]
        args += list(segs)
    in_specs += [q_spec, row_spec, row_spec]
    args += [do3, lse, delta]

    def kernel(*refs):
        refs = list(refs)
        q_ref, k_ref, v_ref, bias_ref = refs[:4]
        nxt = 4
        seed_ref = refs[nxt] if has_drop else None
        nxt += has_drop
        qs_ref = refs[nxt] if has_seg else None
        ks_ref = refs[nxt + 1] if has_seg else None
        nxt += 2 * has_seg
        do_ref, lse_ref, delta_ref, db_ref = refs[nxt:]
        _dbias_kernel(q_ref, k_ref, v_ref, bias_ref, seed_ref, qs_ref,
                      ks_ref, do_ref,
                      lse_ref, delta_ref, db_ref, scale=scale, causal=causal,
                      block_q=block_q, block_k=block_k, swap=swap,
                      offset=sk - sq, dropout_rate=dropout_rate,
                      bh_fn=bh_of)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=db_spec,
        out_shape=_sds(bias4.shape, jnp.float32, q3),
        interpret=_interp(),
    )(*args)


@functools.lru_cache(maxsize=None)
def _make_flash(scale: float, causal: bool, tile: tuple,
                has_bias: bool, need_dbias: bool, h: int,
                dropout_rate: float, has_seg: bool,
                checkpoint_names: bool = False):
    """``tile``: ``(block_q, block_k, sub_q, sub_k)`` (:func:`_auto_block`)."""

    def _segs(qs, ks):
        return (qs, ks) if has_seg else None

    def _fwd(q3, k3, v3, bias4, seed, qseg, kseg):
        return _fwd_pallas(q3, k3, v3, bias4 if has_bias else None, seed,
                           _segs(qseg, kseg), h, scale=scale, causal=causal,
                           tile=tile, dropout_rate=dropout_rate)

    @jax.custom_vjp
    def flash(*args):
        return _fwd(*args)[0]

    def fwd(q3, k3, v3, bias4, seed, qseg, kseg):
        out, lse = _fwd(q3, k3, v3, bias4, seed, qseg, kseg)
        if checkpoint_names:
            # Tag the kernel residuals INSIDE the fwd rule (the trace a
            # name-based jax.checkpoint policy sees under AD). Saving the
            # context alone would not keep the forward kernel out of the
            # recompute — the backward kernels also consume the logsumexp,
            # and an unsaved residual forces the fwd kernel to rerun in
            # the remat region. With both tagged, DCE drops the fwd kernel
            # from the recomputed set entirely (see apex_tpu/remat.py).
            from apex_tpu.remat import tag as _remat_tag
            out = _remat_tag(out, "flash_ctx")
            lse = _remat_tag(lse, "flash_lse")
        return out, (q3, k3, v3, bias4, seed, qseg, kseg, out, lse)

    def bwd(res, do3):
        q3, k3, v3, bias4, seed, qseg, kseg, out, lse = res
        delta = jnp.sum(do3.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1, keepdims=True)
        common = dict(scale=scale, causal=causal, dropout_rate=dropout_rate)
        dq, dk, dv = _bwd_pallas(
            q3, k3, v3, bias4 if has_bias else None, seed,
            _segs(qseg, kseg), h, do3, lse, delta, tile=tile, **common)
        if has_bias and need_dbias:
            dbias = _dbias_pallas(q3, k3, v3, bias4, seed,
                                  _segs(qseg, kseg), h, do3, lse, delta,
                                  block_q=tile[0], block_k=tile[1], **common)
        else:
            # documented: zero unless opted in (scalar placeholder when
            # there is no bias at all)
            dbias = jnp.zeros_like(bias4)
        return (dq, dk, dv, dbias, jnp.zeros_like(seed),
                jnp.zeros_like(qseg), jnp.zeros_like(kseg))

    flash.defvjp(fwd, bwd)
    return flash


def _largest(seq: int, choices) -> int:
    """Largest of ``choices`` dividing ``seq`` (0 if none does)."""
    return next((c for c in choices if seq % c == 0), 0)


# The backward holds one head's dq in VMEM (float32 gather + output block:
# 8 bytes an element, 64 MiB of the v5e's 128 here); longer heads go to XLA
_HEAD_ELEMENTS = 1 << 23


def _auto_block(sq: int, sk: int, d: int, block_q: Optional[int] = None,
                block_k: Optional[int] = None, *, has_bias: bool = False,
                has_seg: bool = False):
    """The ``(block_q, block_k, sub_q, sub_k)`` both kernels walk, or None
    where no tile divides the sequences or a head is too long for the
    backward (the caller then falls back to XLA). ``block_q`` / ``block_k``
    are the caller's explicit grid tile, taken as given.

    Chosen on the v5e at the shapes the benchmark's cells run (my chip
    runs, PR 33, ``scripts/flash_tile_sweep.py``; ms a call, forward /
    backward, 64 heads x 1,024 x 64 bf16 causal): sub-tiles of 512 in a
    grid tile of 1,024 0.392 / 0.644, in one of 512 0.396 / 0.673;
    sub-tiles of 256 0.518 / 0.813, of 128 (with the running max and sum
    still kept as columns) 1.25 / 2.2. The window form at 16 heads x 8,192
    x 128, window 4,096: 2.09 in tiles of 1,024 against 2.68 in tiles of
    512. So: the largest grid tile up to 1,024 that divides the sequence,
    walked in sub-tiles of up to 512."""
    # a bias or segment-id block is sliced along its lanes by a key
    # sub-tile (and, for q ids, by a row sub-tile): only statically, so
    # those calls walk one key (and one row) sub-tile a grid tile, no
    # larger than the 512 they always had
    if sq * d > _HEAD_ELEMENTS:
        return None
    whole_k, whole_q = has_bias or has_seg, has_seg
    edges = (512, 256, 128)
    bq = block_q or (1 if sq == 1 else _largest(
        sq, (1024,) * (not whole_q) + edges + (64, 32, 16, 8)))
    bk = block_k or _largest(sk, (1024,) * (not whole_k) + edges)
    if not (bq and bk):
        return None
    return (bq, bk, bq if whole_q else _largest(bq, edges) or bq,
            bk if whole_k else _largest(bk, edges) or bk)


def flash_attention(q, k, v, bias=None, causal: bool = False,
                    softmax_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    bias_requires_grad: bool = False,
                    dropout_rate: float = 0.0,
                    dropout_seed=None,
                    segment_ids=None,
                    checkpoint_names: bool = False,
                    window: Optional[int] = None):
    """Fused attention over ``(b, h, s, d)`` tensors.

    ``window`` (needs ``causal``) and grouped KV heads (``k``/``v`` with
    ``h_kv < h`` heads, ``h % h_kv == 0``) select the FORWARD-ONLY serving
    form (kernel name ``flash_attention_window``): row ``i`` reads column
    ``j`` while ``0 <= i - j < window`` (its own position counted), KV
    blocks wholly outside the window or above the diagonal are neither
    computed nor fetched, and query head ``g`` reads KV head
    ``g // (h // h_kv)`` in place. No bias, dropout, segments or gradient
    there. Without either the call is what it always was.

    ``segment_ids``: packed-sequence (varlen) attention — the TPU-native
    form of the reference's ``cu_seqlens`` packing
    (``reference:apex/contrib/csrc/fmha/fmha_api.cpp:420``). Pass an int
    array ``(b, s)`` (self-attention) or a ``(q_ids, kv_ids)`` pair; tokens
    attend only within their own segment, masked blockwise in VMEM (O(b·s)
    HBM, never O(s²)). Compose with ``causal`` for packed causal LM batches.

    ``bias``: additive fp32 score bias broadcastable to ``(b, h, sq, sk)``
    (use ``-10000``-filled masks for padding, as the reference softmax does).
    Broadcast dims stay broadcast — a padding mask costs O(b·sk) memory.

    ``bias_requires_grad``: the Pallas path returns **zero** gradient for
    ``bias`` unless this is True (see module docstring). Set it when the
    bias is a learned parameter (ALiBi/relative-position); leave False for
    padding masks to keep the backward O(s·d)-memory.

    ``dropout_rate``/``dropout_seed``: in-kernel attention-probability
    dropout (``philox.cuh`` analog; see module docstring). ``dropout_seed``
    is an int scalar (vary it per step/layer, e.g. from
    :func:`~apex_tpu.transformer.tensor_parallel.random.get_rng_tracker`);
    required when ``dropout_rate > 0``.

    ``checkpoint_names``: emit the ``flash_ctx``/``flash_lse``
    ``jax.ad_checkpoint.checkpoint_name`` tags (registry:
    ``apex_tpu/remat.py``) so a name-based remat policy can keep the
    kernel's residuals resident and the forward kernel out of the
    recomputed set. Off by default so untagged programs stay
    jaxpr-identical to the pre-policy ones.

    Falls back to the XLA reference when shapes aren't tile-aligned (same
    dropout mask and same zero-bias-grad semantics on both paths).
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(d)
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    kv_heads = k.shape[1]
    tile = _auto_block(sq, sk, d, block_q, block_k,
                       has_bias=bias is not None,
                       has_seg=segment_ids is not None)
    if use_pallas is None:
        use_pallas = tile is not None and supports_flash(sq, sk, d, *tile[:2])
    elif use_pallas and tile is None:
        raise ValueError(f"no flash tile divides sequences {sq} x {sk}")
    if window is not None or kv_heads != h:
        if window is not None and (not causal or window < 1):
            raise ValueError("window needs causal=True and window >= 1, "
                             f"got causal={causal}, window={window}")
        if h % kv_heads or v.shape[1] != kv_heads:
            raise ValueError(f"{h} query heads do not group over "
                             f"{k.shape[1]}/{v.shape[1]} KV heads")
        if (bias is not None or dropout_rate > 0.0
                or segment_ids is not None or checkpoint_names):
            raise ValueError("the windowed / grouped-KV form is forward-"
                             "only serving attention: no bias, dropout, "
                             "segment_ids or checkpoint_names")
        if not use_pallas:
            return mha_reference(q, k, v, None, causal, softmax_scale,
                                 window=window)
        with jax.named_scope("flash_attention_window"):
            out, _ = _fwd_pallas(
                q.reshape(b * h, sq, d), k.reshape(b * kv_heads, sk, d),
                v.reshape(b * kv_heads, sk, d), None, None, None, h,
                scale=float(softmax_scale), causal=bool(causal),
                tile=tile, dropout_rate=0.0, window=window,
                kv_heads=kv_heads)
        return out.reshape(b, h, sq, d)
    if not use_pallas:
        # honor bias_requires_grad here too so gradient semantics do not
        # silently flip with tile alignment
        if bias is not None and not bias_requires_grad:
            bias = jax.lax.stop_gradient(bias)
        out = mha_reference(q, k, v, bias, causal, softmax_scale,
                            dropout_rate=dropout_rate,
                            dropout_seed=dropout_seed,
                            segment_ids=segment_ids)
        if checkpoint_names:
            # no custom_vjp on the XLA path — tagging the context still
            # lets name policies keep it resident (the plain-op attention
            # body is recomputed, which is exactly XLA ops, no kernel)
            from apex_tpu.remat import tag as _remat_tag
            out = _remat_tag(out, "flash_ctx")
        return out

    q3 = q.reshape(b * h, sq, d)
    k3 = k.reshape(b * h, sk, d)
    v3 = v.reshape(b * h, sk, d)
    has_bias = bias is not None
    if has_bias:
        bias4 = jnp.asarray(bias, jnp.float32)
        if bias4.ndim > 4:
            raise ValueError(f"bias rank {bias4.ndim} > 4")
        while bias4.ndim < 4:
            bias4 = bias4[None]
        for ax, (dim, full) in enumerate(zip(bias4.shape, (b, h, sq, sk))):
            if dim not in (1, full):
                raise ValueError(
                    f"bias dim {ax} is {dim}; must be 1 or {full}")
        if bias4.shape[3] == 1 and sk > 1:
            # keys dim must be materialized for the (…, block_k) tiles
            bias4 = jnp.broadcast_to(bias4, (*bias4.shape[:3], sk))
    else:
        bias4 = jnp.zeros((), jnp.float32)  # placeholder pytree leaf
    if dropout_rate > 0.0:
        # (hi, lo) fp32 pair (SMEM-friendly and a differentiable
        # placeholder for custom_vjp); full 32-bit seed space composed with
        # per-element counters (ADVICE r2: was 24-bit)
        seed = _pack_seed(dropout_seed)
    else:
        seed = jnp.zeros((2,), jnp.float32)
    has_seg = segment_ids is not None
    if has_seg:
        q_ids, kv_ids = _norm_segment_ids(segment_ids, sq, sk)
        # fp32 carrier: exact for id counts < 2**24, and custom_vjp wants
        # float cotangents for every primal
        qseg = q_ids.astype(jnp.float32).reshape(b, 1, sq)
        kseg = kv_ids.astype(jnp.float32).reshape(b, 1, sk)
    else:
        qseg = kseg = jnp.zeros((), jnp.float32)  # placeholder leaf
    fn = _make_flash(float(softmax_scale), bool(causal), tile,
                     has_bias, bool(bias_requires_grad), h,
                     float(dropout_rate), has_seg,
                     bool(checkpoint_names))
    with jax.named_scope("flash_attention"):
        out = fn(q3, k3, v3, bias4, seed, qseg, kseg)
    return out.reshape(b, h, sq, d)


# ---------------------------------------------------------------------------
# decode — single-query attention over the paged KV pool
# ---------------------------------------------------------------------------
#
# The serving fast path (docs/SERVING.md). The training kernels above are
# built for sq == sk score tiles; autoregressive decode is the opposite
# regime — ONE query row per sequence against a long cached key stripe, a
# memory-bound streaming reduction with no backward pass (the reference
# ships a separate inference attention family, fmhalib /
# fast_multihead_attn, for exactly this reason). The cache is read BEFORE
# the new token is appended: the kernel returns the per-row logsumexp and
# the caller folds the CURRENT token's k/v in with one exact two-way LSE
# merge (``_merge_current``; ``_merge_drafts`` for a verify window), so
# the kernel never needs a variable-position write. Empty rows (length 0)
# return lse = -inf, the correct identity for that merge (the training
# kernel's +inf convention exists only for its backward).

def _dequant(x, scale):
    """int8 cache block -> fp32 against per-(position, head) scales
    ``(b, h, T)``."""
    return x.astype(jnp.float32) * scale[..., None]


def _merge_current(out, lse, q, k_new, v_new, scale, out_dtype):
    """Exact two-way logsumexp merge of the cached-prefix attention
    ``(out, lse)`` with the CURRENT token's ``(k_new, v_new)`` — the new
    token always attends to itself, and merging here (instead of writing
    it into the cache first) keeps the kernel free of variable-position
    writes. All fp32; an empty prefix (lse == -inf) reduces to exactly
    ``v_new``."""
    q32 = q.astype(jnp.float32)
    s_new = jnp.sum(q32 * k_new.astype(jnp.float32), axis=-1) * scale  # (b,h)
    m = jnp.maximum(lse, s_new)
    a_old = jnp.exp(lse - m)           # 0 when the prefix is empty
    a_new = jnp.exp(s_new - m)
    merged = (a_old[..., None] * out.astype(jnp.float32)
              + a_new[..., None] * v_new.astype(jnp.float32))
    return (merged / (a_old + a_new)[..., None]).astype(out_dtype)


def _merge_drafts(out, lse, q, k_new, v_new, k_cast, v_cast, scale,
                  out_dtype):
    """Exact (q_len+1)-way logsumexp merge for the speculative verify
    path: fold the cached-prefix attention ``(out, lse)`` — per draft
    row — with the q_len IN-FLIGHT tokens' keys/values, causally masked
    so row i attends rows 0..i (itself plus the earlier drafts). None of
    the in-flight tokens are in the cache yet; a sequential decode would
    have round-tripped rows j < i through the cache's storage dtype
    before row i read them, so the caller passes ``k_cast``/``v_cast``
    (the store+load images of ``k_new``/``v_new``) and the merge uses
    those OFF-diagonal while the diagonal (self-attention) stays fresh —
    exactly the numerics of k single-token steps. Reduces to
    ``_merge_current`` at q_len == 1.

    Shapes: out/q/k_new/v_new/k_cast/v_cast ``(b, h, q_len, d)``, lse
    ``(b, h, q_len)``."""
    q32 = q.astype(jnp.float32)
    qlen = q.shape[2]
    # off-diagonal scores against the cache-dtype images; diagonal fresh
    s_cast = jnp.einsum("bhid,bhjd->bhij", q32,
                        k_cast.astype(jnp.float32)) * scale
    s_self = jnp.sum(q32 * k_new.astype(jnp.float32), axis=-1) * scale
    row = jax.lax.broadcasted_iota(jnp.int32, (qlen, qlen), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (qlen, qlen), 1)
    below = col < row                              # strictly-earlier drafts
    s_off = jnp.where(below, s_cast, -jnp.inf)
    m = jnp.maximum(lse, jnp.maximum(s_self, jnp.max(s_off, axis=-1)))
    a_old = jnp.exp(lse - m)                       # 0 when prefix empty
    p_self = jnp.exp(s_self - m)
    p_off = jnp.where(below, jnp.exp(s_cast - m[..., None]), 0.0)
    denom = a_old + p_self + jnp.sum(p_off, axis=-1)
    merged = (a_old[..., None] * out.astype(jnp.float32)
              + p_self[..., None] * v_new.astype(jnp.float32)
              + jnp.einsum("bhij,bhjd->bhid", p_off,
                           v_cast.astype(jnp.float32)))
    return (merged / denom[..., None]).astype(out_dtype)


def _gathered_reference(q, k, v, lengths, k_new, v_new, k_scale, v_scale,
                        softmax_scale, k_cast, v_cast):
    """The paged kernel's XLA oracle over a slot-major gather of the
    pool: ``q`` ``(b, h, d)`` or ``(b, h, q_len, d)``, ``k``/``v`` ``(b,
    h, T, d)`` (int8 with ``k_scale``/``v_scale`` ``(b, h, T)``), one
    masked score pass over the positions below ``lengths`` and the same
    merge of the in-flight tokens as the kernel's caller makes (and the
    same math as :func:`mha_reference`'s ``kv_length`` path — the parity
    tests pin all three together)."""
    multi = q.ndim == 4
    T = k.shape[2]
    quantized = k.dtype == jnp.int8
    kd = _dequant(k, k_scale) if quantized else k
    vd = _dequant(v, v_scale) if quantized else v
    if multi:
        # verify path: q_len rows per slot, ONE pass over the cached
        # prefix (the mask is the same for every row — none of the
        # in-flight tokens are in the cache), then the causal merge
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       kd.astype(jnp.float32)) * softmax_scale
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, T), 3)
        valid = col < lengths[:, None, None, None]
        s = jnp.where(valid, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.where(valid, jnp.exp(s - m), 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        safe_l = jnp.where(l == 0.0, 1.0, l)
        out = jnp.einsum("bhqk,bhkd->bhqd", p / safe_l,
                         vd.astype(jnp.float32))
        lse = jnp.where(lengths[:, None, None] == 0, -jnp.inf,
                        (m + jnp.log(safe_l))[..., 0])
        if k_new is not None:
            out = _merge_drafts(
                out, lse, q, k_new, v_new,
                k_new if k_cast is None else k_cast,
                v_new if v_cast is None else v_cast,
                float(softmax_scale), q.dtype)
        return out.astype(q.dtype)
    s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                   kd.astype(jnp.float32)) * softmax_scale
    col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
    valid = col < lengths[:, None, None]
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    # fully-masked rows have m == NEG_INF and exp(s - m) == 1 on
    # every entry — zero them explicitly (the kernels' rule)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhk,bhkd->bhd", p / safe_l,
                     vd.astype(jnp.float32))
    lse = jnp.where(lengths[:, None] == 0, -jnp.inf,
                    (m + jnp.log(safe_l))[..., 0])
    if k_new is not None:
        out = _merge_current(out, lse, q, k_new, v_new,
                             float(softmax_scale), q.dtype)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# paged decode kernel — live-block attention over a block-pool KV cache
# ---------------------------------------------------------------------------
#
# The serving kernel (docs/SERVING.md "Paged serving"): vLLM-style
# PagedAttention (Kwon et al.) brought to Pallas. A kernel that streams a
# per-slot ``(max_len, d)`` stripe can only SKIP the compute past the
# cursor — its pipelined HBM fetches stay O(max_len). Here the cache is
# the engine's whole block pool, taken AS IT IS STORED —
# ``(layers, num_blocks, block_size, h * d)``, token-major, all layers
# stacked — and each slot owns an int32 row of pool indices (its block
# table), so:
#
# - why that shape: a Mosaic call demands its operands row-major with the
#   last dimension on the 128 lanes. A pool whose last dimension is d = 64
#   half-fills a lane tile, so XLA keeps such an array with the block axis
#   on the lanes instead (layout ``{3,4,2,1,0}``) and has to relay every
#   layer's slice to a lane-PADDED row-major image before the kernel may
#   read it (twice the bytes, written and read again, every layer, every
#   step). With the heads fused into the last dimension (h * d lanes, a
#   multiple of 128 at every published width) the resident layout IS the
#   row-major one and nothing in the decode program slices, copies or
#   relays the pool: the kernel takes the stacked pool and a LAYER INDEX;
# - the layer index, the per-slot block table, the cursor and the work
#   list ride as SCALAR-PREFETCH arguments
#   (``pltpu.PrefetchScalarGridSpec``): they are resident before the grid
#   starts, and the K/V BlockSpec index maps read them to aim each fetch
#   at ``(layer, table[slot, j], 0, 0)`` — one whole ``(block_size, h *
#   d)`` pool block, all heads, lane-dense;
# - the grid follows the LIVE blocks, not the table's capacity
#   (:func:`paged_work_list`): the cursors become a list of ``(slot,
#   logical block)`` items, one for every block that holds a position the
#   slot's rows still read, slot-major, blocks ascending, and their count
#   ``n_live``. The list is the same for every layer of a kind, so a
#   model builds it once a step, outside its layer scan. The grid is ONE
#   dimension whose bound is ``n_live``, a traced value: the bound is
#   data, not shape, so one compiled program serves every load, and a
#   call costs what its contexts hold — grid steps and HBM traffic are
#   O(actual context), not O(max_seqs x table span). Item ``w`` fetches
#   pool block ``table[slot[w], block[w]]`` and maps the query and output
#   tiles to ``slot[w]``, so a slot's consecutive items keep its tiles
#   resident; the accumulators start on a slot's first item, the division
#   and the write happen on its last. A slot with no item is never
#   visited and its output tile never written: the diagonal select after
#   the call gives it ``out`` 0 and ``lse`` -inf, the identity of the
#   ``_merge_current`` fold;
# - all heads of a block are scored at once and no lane is ever sliced:
#   the query comes in BLOCK-DIAGONAL, ``(h, h * d)`` with head g's row
#   holding q[g] in lanes [g*d, (g+1)*d) and exact zeros elsewhere, so
#   ``q_bd @ K^T`` is the ``(h, block_size)`` per-head score tile. The
#   other heads' keys are multiplied by exact zeros, so each score — and
#   with it m, l and the per-head ``lse`` — holds its own head's products
#   only. ``p @ V`` is ``(h, h * d)``: row g carries head g's weights
#   over EVERY head's values; the caller keeps the diagonal ``d``-wide
#   pieces (a select, not a product: the cross-head pieces are masked
#   before anything is summed);
# - the online-softmax recurrence is ``_fwd_kernel``'s, one query row
#   wide; an int8 pool is dequantized blockwise (the pooled
#   per-(position, head) scales ride ``(layers, num_blocks, h,
#   block_size)`` and scale the score tile and the weights, which is the
#   dequantized product reassociated) — HBM only ever holds int8, which
#   is where a decode step's bytes go. The parity tests pin it, the
#   -inf empty-row convention and the merge with the current token to
#   ``mha_reference(kv_length=)``;
# - ``mean_context`` (an expected-occupancy hint, tokens) sizes the
#   ``pl.CostEstimate`` attached to the kernel so the pyprof roofline
#   prices the live blocks' traffic instead of the worst-case table span
#   (``pyprof/model.py`` reads it off the ``pallas_call`` eqn). It never
#   changes the math — only the modeled bytes.

class PagedWork(NamedTuple):
    """The paged kernel's walk (:func:`paged_work_list`): item ``w <
    n_live`` is logical block ``block[w]`` of slot ``slot[w]``; the
    entries past ``n_live`` are zeros (in range, never computed on).
    ``count`` is the items a slot, 0 where the kernel never visits."""
    slot: jnp.ndarray       # (S * blocks a slot + 1,) int32
    block: jnp.ndarray      # the same
    count: jnp.ndarray      # (S,) int32
    n_live: jnp.ndarray     # () int32


def _walk_blocks(n_blocks: int, block_size: int,
                 window: Optional[int]) -> int:
    """The most blocks one slot's rows read: the table's width, or under
    a window of W positions before a cursor the blocks those can span."""
    if window is None:
        return n_blocks
    return min(n_blocks, (max(window, 2) - 2) // block_size + 2)


def paged_work_list(lengths, block_size: int, n_blocks: int,
                    window: Optional[int] = None) -> PagedWork:
    """The blocks :func:`paged_decode_attention` computes on, from the
    cursors alone: for slot ``s`` the logical blocks ``j`` with ``j *
    block_size < lengths[s]``, under a ``window`` from the first block the
    cursor's row still reads, slot-major, blocks ascending. ``n_blocks``
    is the block table's width. A cumulative sum and a search over a few
    hundred int32, on the device; the same for every layer of a kind, so
    build it once a step, outside the layer scan, and hand it down."""
    lengths = jnp.asarray(lengths, jnp.int32)
    S = lengths.shape[0]
    per_slot = _walk_blocks(n_blocks, block_size, window)
    with jax.named_scope("paged_work_list"):
        first = jnp.zeros_like(lengths) if window is None \
            else jnp.maximum(lengths - window + 1, 0) // block_size
        count = jnp.clip((lengths + block_size - 1) // block_size - first,
                         0, per_slot)
        ends = jnp.cumsum(count)
        w = jnp.arange(S * per_slot + 1, dtype=jnp.int32)
        # every item against every slot's end: one small fusion, no loop
        slot = jnp.searchsorted(ends, w, side="right",
                                method="compare_all").astype(jnp.int32)
        live = slot < S
        slot = jnp.where(live, slot, 0)
        block = jnp.where(live, first[slot] + w - (ends - count)[slot], 0)
        return PagedWork(slot, block, count, ends[-1])


def _paged_decode_kernel(layer_ref, tab_ref, len_ref, slot_ref, blk_ref,
                         n_ref, q_ref, k_ref, v_ref, ksc_ref, vsc_ref,
                         o_ref, lse_ref, acc_ref, m_ref, l_ref, *, scale,
                         block_size, q_len, window=None):
    del layer_ref, tab_ref                  # the index maps' business
    w = pl.program_id(0)
    s, j = slot_ref[w], blk_ref[w]
    length = len_ref[s]

    # the slot's first item: its neighbour to the left is another slot's
    @pl.when((w == 0) | (slot_ref[jnp.maximum(w - 1, 0)] != s))
    def _():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    k = k_ref[0, 0].astype(jnp.float32)       # (block_size, h*d)
    v = v_ref[0, 0].astype(jnp.float32)
    pos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    cached = pos < length
    # one (h, h*d) block-diagonal query tile per in-flight row: every
    # array below is (h, ·), the same program at q_len 1 and q_len k
    for i in range(q_len):
        # row i sits at position length + i and, under a window, reads
        # back to length + i - window + 1
        live = cached if window is None \
            else cached & (pos > length + i - window)
        q = q_ref[0, i].astype(jnp.float32)   # (h, h*d)
        s_ = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32
                                 ) * scale    # (h, block_size)
        if ksc_ref is not None:
            # int8 pool: q . (k_q * scale) == (q . k_q) * scale — HBM
            # and VMEM only ever hold int8 blocks
            s_ = s_ * ksc_ref[0, 0]
        s_ = jnp.where(live, s_, NEG_INF)
        m_prev = m_ref[i]
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=1, keepdims=True))
        p = jnp.where(live, jnp.exp(s_ - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[i] = l_ref[i] * corr + jnp.sum(p, axis=1, keepdims=True)
        m_ref[i] = m_new
        if vsc_ref is not None:
            p = p * vsc_ref[0, 0]
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[i] = acc_ref[i] * corr + pv   # (h, h*d)

    # the slot's last item (the entries past n_live are slot 0's, which
    # the last live slot may be too: hence the count)
    @pl.when((w == n_ref[0] - 1) | (slot_ref[w + 1] != s))
    def _():
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        # -inf on empty rows: the identity of the _merge_current fold
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = jnp.where(l == 0.0, -jnp.inf,
                               m_ref[:] + jnp.log(safe_l))


def _paged_cost(s, h, d, kv_dtype, quantized, n_blocks_slot, block_size,
                mean_context, q_len=1, q_itemsize=2, hkv=None):
    """``pl.CostEstimate`` for one paged decode call: the HBM bytes at
    ``mean_context`` tokens of ACTUAL context per slot (the kernel walks
    the live blocks only), so the pyprof roofline prices what the kernel
    moves, not the worst-case table span. The
    K/V fetches are dense ``(block_size, h * d)`` blocks; the query and
    the output ride block-diagonal, ``h`` times their useful size, and
    the FLOPs are those the MXU is issued for them (``h`` times the
    algorithm's: the other heads' lanes are multiplied by zeros).

    ``q_len > 1`` is the speculative verify call: the MXU work and the
    q/out traffic scale by q_len, but the dominant KV stream does NOT —
    the cached stripe is fetched once for all q_len rows, which is
    exactly why the roofline shows the per-token HBM cost dropping ~k×
    at acceptance."""
    cap = n_blocks_slot * block_size
    ctx = cap if mean_context is None else mean_context
    ctx = float(min(max(ctx, 1), cap))
    # fetched context rounds up to whole blocks per slot
    ctx = math.ceil(ctx / block_size) * block_size
    itemsize = jnp.dtype(kv_dtype).itemsize
    hkv = h if hkv is None else hkv      # grouped KV: the pool's heads
    kv_bytes = 2.0 * s * hkv * ctx * d * itemsize
    if quantized:
        kv_bytes += 2.0 * s * hkv * ctx * 4
    io_bytes = (kv_bytes + 2.0 * s * q_len * h * hkv * d * q_itemsize
                + s * q_len * h * 4 + (s * (n_blocks_slot + 1) + 1) * 4)
    flops = 4.0 * s * q_len * h * ctx * hkv * d  # qk^T + pv, 2 MACs each
    return pl.CostEstimate(flops=int(flops), bytes_accessed=int(io_bytes),
                           transcendentals=int(s * h * ctx * q_len))


def _paged_decode_pallas(q, kp, vp, layer, tables, lengths, ksc, vsc, work,
                         *, scale, mean_context, window=None):
    # q is (S, h, q_len, d): q_len == 1 is the classic decode step,
    # q_len == k + 1 the speculative verify — ONE program shape for
    # both. Every block spans its array's last two dims whole — the
    # (block_size, h*d) pool blocks, the (h, h*d) query/output tiles,
    # the (h, block_size) scale tiles, the (h, 1) lse columns — which
    # Mosaic accepts at any size; the walk is q_len-independent.
    S, h, q_len, d = q.shape
    block_size = kp.shape[2]
    has_scale = ksc is not None
    # grouped KV heads: the pool's rows are hkv * d lanes wide and query
    # head g reads KV head g // (h // hkv) where it lies
    hkv = kp.shape[3] // d

    # block-diagonal query (see the section comment): (S, q_len, h, hkv*d)
    # with row g's own d lanes under its KV head and exact zeros elsewhere
    eye = jnp.eye(h, dtype=jnp.bool_) if hkv == h else (
        jnp.arange(h)[:, None] // (h // hkv) == jnp.arange(hkv)[None, :])
    q_bd = jnp.where(eye[None, None, :, :, None],
                     jnp.transpose(q, (0, 2, 1, 3))[:, :, :, None, :],
                     jnp.zeros((), q.dtype)).reshape(S, q_len, h, hkv * d)

    def q_map(w, lay, tabs, lens, slot, blk, n):
        return (slot[w], 0, 0, 0)

    def kv_map(w, lay, tabs, lens, slot, blk, n):
        return (lay[0], tabs[slot[w], blk[w]], 0, 0)

    kv_spec = pl.BlockSpec((1, 1, block_size, hkv * d), kv_map)
    in_specs = [pl.BlockSpec((1, q_len, h, hkv * d), q_map), kv_spec,
                kv_spec]
    args = [q_bd, kp, vp]
    if has_scale:
        sc_spec = pl.BlockSpec((1, 1, hkv, block_size), kv_map)
        in_specs += [sc_spec, sc_spec]
        args += [ksc, vsc]

    def kernel(*refs):
        refs = list(refs)
        prefetch, (q_ref, k_ref, v_ref) = refs[:6], refs[6:9]
        nxt = 9
        ksc_ref = refs[nxt] if has_scale else None
        vsc_ref = refs[nxt + 1] if has_scale else None
        nxt += 2 * has_scale
        o_ref, lse_ref, acc, m, l = refs[nxt:]
        _paged_decode_kernel(*prefetch, q_ref, k_ref, v_ref, ksc_ref,
                             vsc_ref, o_ref, lse_ref, acc, m, l,
                             scale=scale, block_size=block_size,
                             q_len=q_len, window=window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(work.n_live,),
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, q_len, h, hkv * d), q_map),
                   pl.BlockSpec((1, q_len, h, 1), q_map)),
        scratch_shapes=[pltpu.VMEM((q_len, h, hkv * d), jnp.float32),
                        pltpu.VMEM((q_len, h, 1), jnp.float32),
                        pltpu.VMEM((q_len, h, 1), jnp.float32)])
    out_dtype = q.dtype if q.dtype != jnp.int8 else jnp.float32
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((S, q_len, h, hkv * d), out_dtype),
                   jax.ShapeDtypeStruct((S, q_len, h, 1), jnp.float32)),
        cost_estimate=_paged_cost(
            S, h, d, kp.dtype, has_scale,
            _walk_blocks(tables.shape[1], block_size, window), block_size,
            mean_context, q_len=q_len, q_itemsize=q.dtype.itemsize,
            hkv=hkv),
        interpret=_interp(),
        name="paged_decode_attention",
    )(jnp.reshape(layer, (1,)), tables, lengths, work.slot, work.block,
      jnp.reshape(work.n_live, (1,)), *args)
    # keep each head's own d lanes of its (h*d)-wide row: a select, so a
    # cross-head product is dropped before anything could be summed; a
    # slot the walk never visited has no tile written, and reads as the
    # empty prefix it is
    seen = (work.count > 0)[:, None, None]
    keep = eye[None, None, :, :, None] & seen[..., None, None]
    out = jnp.sum(jnp.where(keep, out.reshape(S, q_len, h, hkv, d),
                            jnp.zeros((), out_dtype)), axis=3)
    lse = jnp.where(seen, lse[..., 0], -jnp.inf)
    return (jnp.transpose(out, (0, 2, 1, 3)), jnp.transpose(lse, (0, 2, 1)))


def paged_decode_attention(q, k_pool, v_pool, layer, block_tables, lengths,
                           k_new=None, v_new=None, k_scale=None,
                           v_scale=None,
                           softmax_scale: Optional[float] = None,
                           mean_context: Optional[float] = None,
                           use_pallas: Optional[bool] = None,
                           k_cast=None, v_cast=None,
                           window: Optional[int] = None,
                           work: Optional[PagedWork] = None):
    """Single-query attention over a PAGED KV cache (see the section
    comment above) — the v2 serving decode kernel.

    Grouped KV heads: a pool whose rows are ``h_kv * d`` wide with
    ``h % h_kv == 0`` is read as it lies, query head ``g`` against KV
    head ``g // (h // h_kv)`` (``k_new``/``v_new`` are then ``(b, h_kv,
    d)``). ``window``: the row at cursor ``c`` reads cached positions
    ``p`` with ``c - p < window`` (its own position counts as one of the
    window's); the kernel's walk and fetches cover the window's blocks
    only, so table entries left of it may be null.

    Speculative verify: pass ``q`` as ``(b, h, q_len, d)`` (with rank-4
    ``k_new``/``v_new`` and optional ``k_cast``/``v_cast`` store+load
    images) to score q_len in-flight tokens per slot against ONE walk of
    the cached blocks — the walk is q_len-independent, so the per-token
    HBM cost drops ~q_len× at full acceptance. Returns ``(b, h, q_len,
    d)``.

    Args:
      q: ``(b, h, d)`` — one query row per sequence slot — or
        ``(b, h, q_len, d)`` for the verify path.
      k_pool, v_pool: ``(layers, num_blocks, block_size, h * d)`` — the
        engine's block pools as :class:`~apex_tpu.serving.cache.
        PagedKVCache` stores them (bf16/fp32, or int8 with pooled
        scales), all layers stacked. Only the blocks of ``layer`` a
        slot's table names are ever read for it.
      layer: int32 scalar (traced inside the layer scan, or a Python
        int) — which layer's blocks to read.
      block_tables: ``(b, n_blocks_per_slot)`` int32 — pool indices of
        each slot's logical blocks, in order. Entries past
        ``ceil(length/block_size)`` are never read (the walk ends before
        them); unmapped entries should name the allocator's null block
        (0).
      lengths: ``(b,)`` int32 per-slot cursor — valid cache positions
        (the current token is NOT in the cache; pass it via ``k_new``).
      k_new, v_new: optional ``(b, h, d)`` current token, folded in with
        the exact two-way LSE merge (empty prefix reduces to ``v_new``).
      k_scale, v_scale: ``(layers, num_blocks, h, block_size)`` fp32
        pooled dequantization scales, required iff the pool dtype is
        int8.
      mean_context: expected ACTUAL context per slot (tokens), used only
        to size the kernel's ``CostEstimate`` for the pyprof roofline —
        never changes the math. Default: the worst-case table span.
      work: :func:`paged_work_list` of these ``lengths``, this table's
        width and this ``window``; a caller with a layer scan builds it
        once, outside. Default: built here.

    Returns ``(b, h, d)`` in ``q.dtype``.

    ``use_pallas=None`` means the kernel. ``use_pallas=False`` selects a
    gather-then-reference XLA path (same math, priced O(table span)) —
    the parity oracle, never auto-selected.
    """
    multi = q.ndim == 4
    if multi:
        b, h, q_len, d = q.shape
    else:
        b, h, d = q.shape
        q_len = 1
    if k_pool.ndim != 4 or v_pool.shape != k_pool.shape \
            or k_pool.shape[3] % d or h % (k_pool.shape[3] // d):
        raise ValueError(f"pool shapes {k_pool.shape}/{v_pool.shape} are "
                         f"not (layers, num_blocks, block_size, h_kv * d) "
                         f"with h_kv dividing h for q {q.shape}")
    hkv = k_pool.shape[3] // d
    if (window is not None or hkv != h) and (
            multi or k_pool.dtype == jnp.int8):
        raise ValueError("window / grouped KV heads are served for one "
                         "query row over an unquantized pool (no "
                         "speculative verify, no int8 cache)")
    block_size = k_pool.shape[2]
    if block_tables.ndim != 2 or block_tables.shape[0] != b:
        raise ValueError(f"block_tables must be (b, n_blocks_per_slot), "
                         f"got {block_tables.shape}")
    quantized = k_pool.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 pools need k_scale/v_scale")
    if softmax_scale is None:
        softmax_scale = 1.0 / math.sqrt(d)
    if use_pallas is None:
        # no shape is refused: every block of the kernel spans its array's
        # last two dims whole, which Mosaic accepts at any size. Small
        # blocks are legal, not fast: h * d % 128 == 0 keeps the pool
        # blocks lane-dense, block_size % 128 == 0 the score tile.
        use_pallas = True
    layer = jnp.asarray(layer, jnp.int32)
    block_tables = jnp.asarray(block_tables).astype(jnp.int32)
    lengths = jnp.asarray(lengths).astype(jnp.int32)
    if use_pallas and work is None:
        work = paged_work_list(lengths, block_size, block_tables.shape[1],
                               window)

    with jax.named_scope("decode_attention"):
        if use_pallas:
            out, lse = _paged_decode_pallas(
                q if multi else q[:, :, None, :], k_pool, v_pool, layer,
                block_tables, lengths,
                k_scale if quantized else None,
                v_scale if quantized else None, work,
                scale=float(softmax_scale), mean_context=mean_context,
                window=window)
            if not multi:
                out, lse = out[:, :, 0], lse[:, :, 0]
            if k_new is not None and multi:
                out = _merge_drafts(
                    out, lse, q, k_new, v_new,
                    k_new if k_cast is None else k_cast,
                    v_new if v_cast is None else v_cast,
                    float(softmax_scale), q.dtype)
            elif k_new is not None:
                if hkv != h:
                    # one token's (b, h_kv, d) row a query head: tiny
                    k_new = jnp.repeat(k_new, h // hkv, axis=1)
                    v_new = jnp.repeat(v_new, h // hkv, axis=1)
                out = _merge_current(out, lse, q, k_new, v_new,
                                     float(softmax_scale), q.dtype)
            return out.astype(q.dtype)
        # XLA fallback: gather the layer's table-mapped blocks slot-major
        # and run one masked score pass + the same merge — identical
        # math, O(table span) traffic
        T = block_tables.shape[1] * block_size
        if window is not None or hkv != h:
            # the oracle of the windowed / grouped form: the cached
            # positions the cursor's row reads and the current token as
            # one more column, one plain softmax
            def dense(pool, new):
                g = pool[layer][block_tables].reshape(b, T, hkv, d)
                g = jnp.concatenate([g, new[:, None].astype(g.dtype)], 1)
                return jnp.repeat(g.transpose(0, 2, 1, 3), h // hkv, 1)
            pos = jnp.arange(T + 1)[None, :]
            cur = lengths[:, None]
            seen = (pos < cur) | (pos == T)
            if window is not None:
                seen = seen & ((pos > cur - window) | (pos == T))
            bias = jnp.where(seen, 0.0, NEG_INF)[:, None, None, :]
            return mha_reference(q[:, :, None], dense(k_pool, k_new),
                                 dense(v_pool, v_new), bias,
                                 softmax_scale=softmax_scale)[:, :, 0]

        def gather(pool):
            g = pool[layer][block_tables]       # (b, nbs, bs, h*d)
            return g.reshape(b, T, h, d).transpose(0, 2, 1, 3)
        kd = gather(k_pool)
        vd = gather(v_pool)
        ksc = vsc = None
        if quantized:
            def gather_sc(sc):
                g = sc[layer][block_tables]     # (b, nbs, h, bs)
                return g.transpose(0, 2, 1, 3).reshape(b, h, T)
            ksc = gather_sc(k_scale)
            vsc = gather_sc(v_scale)
        return _gathered_reference(q, kd, vd, lengths, k_new, v_new, ksc,
                                   vsc, softmax_scale, k_cast, v_cast)

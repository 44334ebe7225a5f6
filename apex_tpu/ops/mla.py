"""Latent attention (MLA, DeepSeek-V2's multi-head latent attention) pieces
for serving: the YaRN rotary table and the ABSORBED paged decode kernel.

A latent layer caches one row a position for all its heads, ``[c | k_pe]``:
the key-value latent ``c`` (``latent`` wide, after its RMS norm) and the
rotated positional key ``k_pe`` (``rope`` wide, one for every head). Head
``h``'s key is ``[c W_uk[h] | k_pe]`` and its value ``c W_uv[h]``. In
decode the per-head matrices are folded into the query and the output
instead of expanding every cached row:

    q_lat[h] = W_uk[h] q_nope[h]                       (latent wide)
    s_h(t)   = ([q_lat[h] | q_pe[h]] . [c(t) | k_pe(t)]) * scale
    ctx[h]   = sum_t softmax_t(s_h)(t) c(t)            (latent wide)
    o[h]     = ctx[h] W_uv[h]                          (after the kernel)

which is the expanded attention's function, computed in another order.
:func:`mla_decode_attention` reads each cached row ONCE for all heads, for
the score and for the value.

The kernel (``mla_decode_attention``, its own name in a device trace)
walks the live blocks of a paged pool the way
:func:`~apex_tpu.ops.flash_attention.paged_decode_attention` does
(:func:`~apex_tpu.ops.flash_attention.paged_work_list`: a 1-D grid whose
bound is the count of live blocks, the layer index, the block table, the
cursors and the walk as scalar-prefetch arguments): item ``w`` fetches a
block of each of the pool's two stacked leaves where it lies, the
latent's ``(block_size, latent)`` and the positional keys' ``(rope,
block_size)`` (positions on the lanes: see
:class:`~apex_tpu.serving.cache.LatentPagedCache`), and maps the query and
output tiles to its slot, so a slot's items keep them resident. All heads
of a block are scored at once, ``q_lat (heads, latent) x c^T`` plus
``q_pe (heads, rope) x k_pe``, and the context is the probabilities times
the latent block, ``(heads, block_size) x (block_size, latent)``: no
block-diagonal tile, since the row IS every head's key. The
current token's row is in the pool before the call (the caller appends
it first), so ``lengths`` counts it and no merge follows. A slot with no
live position is never visited and reads a context of 0.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.flash_attention import NEG_INF, PagedWork, paged_work_list
from apex_tpu.utils.backend import pallas_interpret as _interp

__all__ = ["yarn_inv_freq", "yarn_mscale", "mla_decode_attention"]


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention factor: ``0.1 mscale ln(factor) + 1`` (1 where the
    table is not stretched)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, factor: float = 1.0,
                  original: int = 0, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """The rotary frequencies of a ``dim``-wide positional part, float32
    ``(dim // 2,)``: ``theta^(-2i/dim)``, and under YaRN (``factor > 1``)
    ``f_i / factor * r_i + f_i * (1 - r_i)`` with ``r_i`` the linear ramp
    from 0 to 1 over DeepSeek's correction range of ``beta_fast`` /
    ``beta_slow`` rotations at ``original`` positions
    (``yarn_find_correction_range``: the dimensions that turn fewer than
    ``beta_slow`` times over the original length are interpolated whole,
    those that turn more than ``beta_fast`` times are kept)."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor <= 1.0:
        return f.astype(np.float32)

    def turn(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(turn(beta_fast)), 0)
    hi = min(math.ceil(turn(beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 0.001), 0, 1)
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def _mla_kernel(layer_ref, tab_ref, len_ref, slot_ref, blk_ref, n_ref,
                ql_ref, qr_ref, c_ref, r_ref, o_ref, acc_ref, m_ref, l_ref, *,
                scale, block_size):
    del layer_ref, tab_ref                  # the index maps' business
    w = pl.program_id(0)
    s, j = slot_ref[w], blk_ref[w]

    # the slot's first item: its neighbour to the left is another slot's
    @pl.when((w == 0) | (slot_ref[jnp.maximum(w - 1, 0)] != s))
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    c = c_ref[0, 0]                                      # (bs, latent)
    # the score against the whole row, its two parts as they lie (bfloat16
    # products are exact in the float32 sum)
    s_ = (jax.lax.dot_general(ql_ref[0], c, (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
          + jax.lax.dot_general(qr_ref[0], r_ref[0, 0],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)) * scale
    pos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)
    live = pos < len_ref[s]
    s_ = jnp.where(live, s_, NEG_INF)                    # (heads, bs)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s_, axis=1, keepdims=True))
    p = jnp.where(live, jnp.exp(s_ - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_ref[...] = m_new
    pv = jax.lax.dot_general(p, c.astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv              # (heads, latent)

    # the slot's last item (the entries past n_live are slot 0's, which the
    # last live slot may be too: hence the count)
    @pl.when((w == n_ref[0] - 1) | (slot_ref[w + 1] != s))
    def _():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _mla_cost(S, h, latent, rope, itemsize, n_blocks_slot, block_size,
              mean_context):
    """``pl.CostEstimate`` of one call at ``mean_context`` positions a slot
    (the worst-case span where it is not given): the rows once, the
    queries in and the contexts out; ``2 h (2 latent + rope)`` FLOPs a
    position."""
    ctx = n_blocks_slot * block_size if mean_context is None \
        else min(max(float(mean_context), 1.0), n_blocks_slot * block_size)
    ctx = math.ceil(ctx / block_size) * block_size
    return pl.CostEstimate(
        flops=int(2.0 * S * h * ctx * (2 * latent + rope)),
        bytes_accessed=int(S * ctx * (latent + rope) * itemsize
                           + S * h * (2 * latent + rope) * itemsize),
        transcendentals=int(S * h * ctx))


def _mla_pallas(q_lat, q_pe, c_pool, r_pool, layer, tables, lengths, work,
                *, scale, mean_context):
    S, h, latent = q_lat.shape
    rope, block_size = r_pool.shape[2], r_pool.shape[3]

    def slot_map(w, lay, tabs, lens, slot, blk, n):
        return (slot[w], 0, 0)

    def block_map(w, lay, tabs, lens, slot, blk, n):
        return (lay[0], tabs[slot[w], blk[w]], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(work.n_live,),
        in_specs=[pl.BlockSpec((1, h, latent), slot_map),
                  pl.BlockSpec((1, h, rope), slot_map),
                  pl.BlockSpec((1, 1, block_size, latent), block_map),
                  pl.BlockSpec((1, 1, rope, block_size), block_map)],
        out_specs=pl.BlockSpec((1, h, latent), slot_map),
        scratch_shapes=[pltpu.VMEM((h, latent), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32)])
    kernel = lambda *refs: _mla_kernel(*refs, scale=scale,
                                       block_size=block_size)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, h, latent), q_lat.dtype),
        cost_estimate=_mla_cost(S, h, latent, rope, c_pool.dtype.itemsize,
                                tables.shape[1], block_size, mean_context),
        interpret=_interp(),
        name="mla_decode_attention",
    )(jnp.reshape(layer, (1,)), tables, lengths, work.slot, work.block,
      jnp.reshape(work.n_live, (1,)), q_lat, q_pe, c_pool, r_pool)
    # a slot the walk never visited has no tile written: context 0
    return jnp.where((work.count > 0)[:, None, None], out,
                     jnp.zeros((), out.dtype))


def _mla_xla(q_lat, q_pe, c_pool, r_pool, layer, tables, lengths, *, scale):
    """The kernel's XLA form and oracle: the slot's blocks gathered, one
    masked softmax in float32."""
    S = q_lat.shape[0]
    hi = jax.lax.Precision.HIGHEST
    c = c_pool[layer][tables].astype(jnp.float32)   # (S, blocks, bs, latent)
    c = c.reshape(S, -1, c.shape[-1])
    r = r_pool[layer][tables].astype(jnp.float32)   # (S, blocks, rope, bs)
    r = r.transpose(0, 1, 3, 2).reshape(S, -1, r.shape[2])
    s = (jnp.einsum("shc,stc->sht", q_lat.astype(jnp.float32), c,
                    precision=hi)
         + jnp.einsum("shr,str->sht", q_pe.astype(jnp.float32), r,
                      precision=hi)) * scale
    live = (jnp.arange(c.shape[1])[None, :] < lengths[:, None])[:, None]
    s = jnp.where(live, s, NEG_INF)
    p = jnp.where(live, jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    l = jnp.sum(p, -1, keepdims=True)
    ctx = jnp.einsum("sht,stc->shc", p / jnp.where(l == 0.0, 1.0, l), c,
                     precision=hi)
    return ctx.astype(q_lat.dtype)


def mla_decode_attention(q_lat, q_pe, latent_pool, rope_pool, layer,
                         block_tables, lengths, *, softmax_scale: float,
                         work: Optional[PagedWork] = None,
                         use_pallas: Optional[bool] = None,
                         mean_context: Optional[float] = None):
    """One query row a slot against its cached latent rows (module
    docstring): ``q_lat`` ``(S, heads, latent)`` and ``q_pe`` ``(S, heads,
    rope)`` the absorbed queries; ``latent_pool`` ``(layers, blocks,
    block_size, latent)`` and ``rope_pool`` ``(layers, blocks, rope,
    block_size)`` (:class:`~apex_tpu.serving.cache.LatentPagedCache`), read
    at ``layer`` (int32 scalar) through ``block_tables`` ``(S, blocks a
    slot)``; ``lengths`` ``(S,)`` the positions each slot reads, its
    current row included (0: the slot is not served). Returns the contexts
    ``(S, heads, latent)`` in ``q_lat.dtype``.

    ``work``: :func:`~apex_tpu.ops.flash_attention.paged_work_list` of
    these ``lengths`` (a caller with a layer scan builds it once, outside).
    ``use_pallas=None`` means the kernel; ``False`` the gathered XLA form,
    the kernel's oracle (O(table span), never chosen by itself).
    ``mean_context`` sizes the kernel's cost estimate only."""
    S, h, latent = q_lat.shape
    if latent_pool.ndim != 4 or latent_pool.shape[3] != latent \
            or rope_pool.shape != latent_pool.shape[:2] + (
                q_pe.shape[-1], latent_pool.shape[2]):
        raise ValueError(f"pools {latent_pool.shape} / {rope_pool.shape} "
                         f"do not hold the rows of queries {q_lat.shape} / "
                         f"{q_pe.shape}")
    layer = jnp.asarray(layer, jnp.int32)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    args = (q_lat, q_pe, latent_pool, rope_pool, layer, block_tables,
            lengths)
    with jax.named_scope("mla_decode_attention"):
        if use_pallas is False:
            return _mla_xla(*args, scale=float(softmax_scale))
        if work is None:
            work = paged_work_list(lengths, latent_pool.shape[2],
                                   block_tables.shape[1])
        return _mla_pallas(*args, work, scale=float(softmax_scale),
                           mean_context=mean_context)

"""The paged KV cache: a block pool on the device, an allocator on the host.

:class:`PagedKVCache` is ONE global ``(num_layers, num_blocks,
block_size, H * D)`` block pool per K and V; which pool blocks a slot
owns is host-side state in :class:`BlockAllocator` (per-slot int32 block
tables + cursors, refcounts, a chained prefix-hash index for
copy-on-write prompt sharing). The device pytree holds ONLY the pool
(+ scales) — tables and cursors ride as plain array arguments of the
AOT serving programs, so every program over it is FIXED SHAPE:
admission, retirement, block growth, variable sequence lengths, prefix
sharing and COW are all expressed through those arguments, never through
array shapes, and nothing ever recompiles. Block index 0 is the
allocator's reserved NULL block: unmapped table entries and masked
writes land there, keeping every device program total. A pool of
``max_seqs * ceil(max_len / block_size) + 1`` blocks holds every slot's
whole ``max_len`` (the engine's default); a smaller one serves the same
slots at the traffic's mean length.

``dtype=jnp.int8`` stores the pool quantized with per-(position, head)
fp32 scales (symmetric absmax over the head dim, quantized at write
time — every token is quantized against its own range, so there is no
prefill-vs-decode calibration order to get wrong). HBM cost per token
drops 2x vs bf16 at ~6% scale overhead; the decode kernel dequantizes
blockwise in VMEM.

**Pools by layer kind**: a model built from a layer pattern
(:mod:`apex_tpu.models.pattern_decoder`) keeps one pool and one block
table per layer KIND (:class:`KindPagedKVCache`,
:class:`KindBlockAllocator`): a window layer reads only the last
``window`` positions, so its allocator (``BlockAllocator(window=)``) never
maps a prompt block that lies wholly left of the window and hands a block
back the moment the cursor has left it for good, while a full layer's
table keeps every block until ``release``. One cursor a slot, a table a
kind. A looped stack (the same layers run ``loops`` times over their own
output) keeps a K/V layer a pass: its kind's pool stacks ``loops *
layers`` layers (the model's ``cache_kinds`` says so), and every count of
the pool's bytes follows from that shape.

**A state kind** (PR 35): a recurrent layer (Mamba-2, Mamba-1) keeps no
blocks: what a slot needs of the past is a FIXED-SIZE state whatever its
context, the conv's last inputs and the SSM state, one row a slot a layer
(:class:`SlotStateCache`, described by a :class:`StateSpec` among the
model's ``cache_kinds``). The state's shape is the MODEL's
(``StateSpec.layout``): Mamba-2's ``(heads, head_dim, 128)`` ends in its
state size, Mamba-1's state of 16 is held channels last, ``(16,
channels)``, so that neither pads its lanes. It lives in the same :class:`KindPagedKVCache`
beside the block pools, donated and updated in place like them; the
allocator gives it no blocks and no table (the slot IS its address), the
prefill program is told its slot and overwrites the slot's rows (a slot
given anew starts from zero whatever it held), the decode step advances the
rows of the slots it serves and leaves the others as they were.

**A latent kind**: a latent-attention layer (MLA) caches ONE row a
position for all its heads, the compressed key-value latent and the
rotated positional key side by side (:class:`LatentPagedCache`, described
by a :class:`LatentSpec`), in blocks like a KV kind: its allocator, table
and cursor are a block kind's, without a window. Expanding the row into
per-head K and V would cache ``heads * (qk + v) / width`` times the bytes
(25.6 at Mistral Small 4's 32 heads and 320-wide row); the decode kernel
(:func:`apex_tpu.ops.mla.mla_decode_attention`) reads the row once for all
heads.

The pool is token-major with the heads fused into its last axis because
that is the one layout the append, the layer scan and the decode kernel
all take as it is (measured and compiled for PR 27; the earlier
``(L, NB, H, block_size, D)`` pool was rewritten whole, 6 GB, twice a
decode step):

- the block axis may not become the lane axis. An array whose last
  dimension is ``D = 64`` half-fills a 128-lane tile, so XLA keeps it
  with ``block_size`` on the lanes (layout ``{3,4,2,1,0}``), while a
  Mosaic kernel demands its operands row-major with the last dimension
  padded to 128 lanes — every layer's slice was relaid for the kernel.
  ``H * D`` is a multiple of 128 at every published width: the resident
  layout is the kernel's;
- a write that spans all layers relays the pool: write per layer.
  ``pool.at[:, ids, :, offs, :].set`` is run in a layout with the layer
  axis minor (copy there, scatter, copy back); ``pool.at[layer, ids,
  offs, :].set`` of whole ``(H * D,)`` rows is run in place. So the
  pool is CARRIED through the layer scan and each layer appends its own
  row (:meth:`PagedKVCache.append`), and :meth:`PagedKVCache.cow_copy`
  is a loop of slices, not a gather and a scatter.
"""

from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["cache_bytes_per_slot", "PagedKVCache", "StateSpec",
           "SlotStateCache", "LatentSpec", "LatentPagedCache",
           "KindPagedKVCache", "BlockAllocator", "KindBlockAllocator",
           "AdmitPlan", "StepPlan", "PoolExhausted", "paged_block_bytes",
           "store_roundtrip"]

# floor for the absmax quantization scale: keeps an all-zero row (e.g. a
# never-written slot) from producing 0/0 at dequantization
_MIN_SCALE = 1e-8


def _quantize(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 over the trailing (head) dim: ``(..., D)`` ->
    ``(int8 (..., D), fp32 scale (...))``."""
    scale = jnp.maximum(
        jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0,
        _MIN_SCALE)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def store_roundtrip(x: jnp.ndarray, cache_dtype,
                    quantized: bool) -> jnp.ndarray:
    """The store+load image of ``x``: exactly what a later step would
    read back after this cache appended ``x`` (dtype cast, or int8
    quantize + fp32 dequantize). The speculative verify path feeds this
    to the attention merge for cross-draft keys/values, so one k-token
    verify step reproduces the numerics of k single-token steps — the
    greedy bitwise-stream contract rides on it."""
    if quantized:
        q, scale = _quantize(x)
        return q.astype(jnp.float32) * scale[..., None]
    return x.astype(cache_dtype)


def cache_bytes_per_slot(num_layers: int, num_heads: int, max_len: int,
                         head_dim: int, dtype=jnp.bfloat16) -> int:
    """HBM bytes of ``max_len`` cached positions (k + v across all
    layers, plus the fp32 scales when int8): what one slot's full-length
    reservation pins, and under :func:`paged_block_bytes` one block."""
    per_pos = 2 * num_layers * num_heads * head_dim * jnp.dtype(dtype).itemsize
    if jnp.dtype(dtype) == jnp.int8:
        per_pos += 2 * num_layers * num_heads * 4
    return per_pos * max_len


def paged_block_bytes(num_layers: int, num_heads: int, block_size: int,
                      head_dim: int, dtype=jnp.bfloat16) -> int:
    """HBM bytes of ONE pool block (k + v across all layers, plus the
    fp32 scales when int8) — the unit of the paged capacity math in
    :meth:`apex_tpu.serving.engine.ServingEngine.suggest_pool_blocks`."""
    return cache_bytes_per_slot(num_layers, num_heads, block_size,
                                head_dim, dtype)


# ---------------------------------------------------------------------------
# paged layout: the device-side block pool
# ---------------------------------------------------------------------------

# the reserved null/garbage block: table entry 0 means "unmapped", and
# every masked device write (inactive slot, saturated slot, prompt
# padding past the last real block) is redirected at it — device
# programs stay total and fixed-shape, the allocator simply never hands
# block 0 out
NULL_BLOCK = 0


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKVCache:
    """The paged serving cache: a global block pool in the one
    token-major layout the module docstring derives. Leaves: ``k``,
    ``v`` (+ ``k_scale``/``v_scale`` when quantized); ``num_heads``
    rides as pytree aux data (the pool's last axis is ``H * D`` fused).
    Per-slot block tables and cursors are HOST state
    (:class:`BlockAllocator`) threaded into the AOT programs as plain
    array arguments, never pytree leaves, so they are neither donated
    nor shape-bearing."""

    k: jnp.ndarray                       # (L, NB, block_size, H * D)
    v: jnp.ndarray                       # (L, NB, block_size, H * D)
    num_heads: int
    k_scale: Optional[jnp.ndarray] = None  # (L, NB, H, block_size) fp32
    v_scale: Optional[jnp.ndarray] = None

    # -- pytree protocol ----------------------------------------------------

    def tree_flatten(self):
        if self.quantized:
            return ((self.k, self.v, self.k_scale, self.v_scale),
                    self.num_heads)
        return ((self.k, self.v), self.num_heads)

    @classmethod
    def tree_unflatten(cls, num_heads, leaves):
        k, v, *scales = leaves
        return cls(k, v, num_heads, *scales)

    # -- shape/bookkeeping --------------------------------------------------

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def num_layers(self) -> int:
        return self.k.shape[0]

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]

    @property
    def head_dim(self) -> int:
        return self.k.shape[3] // self.num_heads

    def nbytes(self) -> int:
        """Total pool bytes (the number the paged capacity math sizes)."""
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in self.tree_flatten()[0])

    # -- construction -------------------------------------------------------

    @classmethod
    def create(cls, num_layers: int, num_blocks: int, num_heads: int,
               block_size: int, head_dim: int,
               dtype=jnp.bfloat16) -> "PagedKVCache":
        """Zero-filled pool. ``num_blocks`` INCLUDES the reserved null
        block 0, so the allocatable capacity is ``num_blocks - 1``
        blocks. ``dtype=jnp.int8`` enables the quantized layout."""
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (block 0 is the "
                             f"reserved null block), got {num_blocks}")
        shape = (num_layers, num_blocks, block_size, num_heads * head_dim)
        k = jnp.zeros(shape, dtype)
        v = jnp.zeros(shape, dtype)
        if jnp.dtype(dtype) == jnp.int8:
            # two DISTINCT buffers: a shared array would be donated twice
            # by the AOT steps (XLA rejects duplicate donation)
            sc = (num_layers, num_blocks, num_heads, block_size)
            return cls(k, v, num_heads,
                       jnp.full(sc, _MIN_SCALE, jnp.float32),
                       jnp.full(sc, _MIN_SCALE, jnp.float32))
        return cls(k, v, num_heads)

    # -- writes (device-side, inside the AOT programs) ----------------------

    def _store(self, x: jnp.ndarray):
        if self.quantized:
            return _quantize(x)
        return x.astype(self.k.dtype), None

    def append(self, layer, k_new: jnp.ndarray, v_new: jnp.ndarray,
               block_ids: jnp.ndarray,
               offsets: jnp.ndarray) -> "PagedKVCache":
        """Append one token per slot to layer ``layer`` (an int32
        scalar, traced inside the layer scan): ``k_new``/``v_new`` are
        ``(S, H, D)``, ``block_ids``/``offsets`` ``(S,)`` int32 name the
        pool block and in-block position each slot writes (the HOST
        computes them from its cursor mirror; masked slots point at the
        null block). One scatter per array whose update is ``(S, H * D)``
        whole rows of the pool's lane axis — the form XLA runs in place
        on the resident layout (module docstring: a write that spans all
        layers relays the pool), asserted by the engine's donation lint
        and ``tests/test_chip_compile.py``."""
        kq, ks = self._store(k_new)
        vq, vs = self._store(v_new)
        n = kq.shape[0]
        new = {"k": self.k.at[layer, block_ids, offsets, :].set(
                   kq.reshape(n, -1), mode="drop"),
               "v": self.v.at[layer, block_ids, offsets, :].set(
                   vq.reshape(n, -1), mode="drop")}
        if self.quantized:
            new["k_scale"] = self.k_scale.at[
                layer, block_ids, :, offsets].set(ks, mode="drop")
            new["v_scale"] = self.v_scale.at[
                layer, block_ids, :, offsets].set(vs, mode="drop")
        return dataclasses.replace(self, **new)

    def append_k(self, layer, k_new: jnp.ndarray, v_new: jnp.ndarray,
                 block_ids: jnp.ndarray,
                 offsets: jnp.ndarray) -> "PagedKVCache":
        """Speculative verify append, one layer: up to ``K`` tokens per
        slot — ``k_new``/``v_new`` are ``(S, H, K, D)`` and
        ``block_ids``/``offsets`` ``(S, K)`` int32 name each token's
        pool block and in-block position (HOST-computed by
        :meth:`BlockAllocator.verify_targets`; the window may CROSS a
        block boundary, which is why the ids are per-token, not
        per-slot). Masked tokens — inactive slots, rows past capacity —
        aim at the null block. Every row of the window is written; the
        cursor mirror advances host-side by the ACCEPTED count only
        (:meth:`BlockAllocator.advance_counts`), so rejected rows land
        in slot-private blocks above the cursor."""
        S, H, K, D = k_new.shape

        def rows(x):                       # (S, H, K, D) -> (S*K, H, D)
            return jnp.transpose(x, (0, 2, 1, 3)).reshape(S * K, H, D)

        return self.append(layer, rows(k_new), rows(v_new),
                           block_ids.reshape(S * K), offsets.reshape(S * K))

    def write_prompt_blocks(self, k_new: jnp.ndarray, v_new: jnp.ndarray,
                            block_row: jnp.ndarray) -> "PagedKVCache":
        """Prefill write: ``k_new``/``v_new`` are ``(L, H, P, D)`` for
        ONE slot with ``P`` a multiple of ``block_size``; ``block_row``
        ``(P // block_size,)`` int32 names the destination pool block of
        each prompt chunk (null entries absorb the padding past the last
        real block). Positions past the true prompt length hold padding
        garbage — the cursor masks them from every read. The prompt is
        transposed to token-major once; whole ``(block_size, H * D)``
        blocks then land in place."""
        L, H, P, D = k_new.shape
        bs = self.block_size
        npb = P // bs
        if npb * bs != P:
            raise ValueError(f"prompt window {P} must be a multiple of "
                             f"block_size {bs}")

        def scatter(pool, x):
            # (L, H, P, D) -> (L, NPB, bs, H*D): one advanced index at
            # axis 1 keeps its position, so the update leads with L
            blocks = x.transpose(0, 2, 1, 3).reshape(L, npb, bs, H * D)
            return pool.at[:, block_row].set(blocks, mode="drop")

        kq, ks = self._store(k_new)
        vq, vs = self._store(v_new)
        new = {"k": scatter(self.k, kq), "v": scatter(self.v, vq)}
        if self.quantized:
            def scatter_sc(pool, sc):
                blocks = sc.reshape(L, H, npb, bs).transpose(0, 2, 1, 3)
                return pool.at[:, block_row].set(blocks, mode="drop")
            new["k_scale"] = scatter_sc(self.k_scale, ks)
            new["v_scale"] = scatter_sc(self.v_scale, vs)
        return dataclasses.replace(self, **new)

    def write_layer_blocks(self, layer, k_new: jnp.ndarray,
                           v_new: jnp.ndarray,
                           block_row: jnp.ndarray) -> "PagedKVCache":
        """Prefill write of ONE layer (an int32 scalar traced inside a
        layer scan): ``k_new``/``v_new`` are ``(P, H * D)`` token-major
        with ``P`` a multiple of ``block_size``, ``block_row`` as in
        :meth:`write_prompt_blocks`. Whole ``(block_size, H * D)`` blocks
        land in place; entries that name the null block (padding, blocks
        left of a window) are absorbed there. Unquantized pools only."""
        if self.quantized:
            raise ValueError("write_layer_blocks serves unquantized pools")
        P = k_new.shape[0]
        bs = self.block_size
        if P % bs:
            raise ValueError(f"prompt window {P} must be a multiple of "
                             f"block_size {bs}")
        row = jnp.asarray(block_row, jnp.int32)

        def scatter(pool, x):
            blocks = x.astype(pool.dtype).reshape(P // bs, bs, -1)
            return pool.at[layer, row].set(blocks, mode="drop")

        return dataclasses.replace(self, k=scatter(self.k, k_new),
                                   v=scatter(self.v, v_new))

    def cow_copy(self, src: jnp.ndarray, dst: jnp.ndarray) -> "PagedKVCache":
        """Copy-on-write resolution: pool block ``dst[s] <- src[s]`` per
        slot, BEFORE this step's reads and append (the caller sequences
        it first). The null no-op is ``src == dst == 0`` — block 0 onto
        itself — so a step with no pending COW runs the identical
        program (zero-recompile across admit/COW/retire).

        One slot at a time, a ``(L, 1, ...)`` slice read and written
        back: the loop form stays in place where a gather-and-scatter of
        all pairs asks for a relaid copy of the pool. Slot order is
        safe: a pair's ``dst`` is freshly taken, and the only block a
        later pair may reuse as its ``dst`` is an earlier pair's
        released ``src``, which has been read by then."""
        def copy(pool):
            size = (pool.shape[0], 1) + pool.shape[2:]
            zeros = (0,) * (pool.ndim - 2)

            def move(s, pool):
                block = jax.lax.dynamic_slice(
                    pool, (0, src[s]) + zeros, size)
                return jax.lax.dynamic_update_slice(
                    pool, block, (0, dst[s]) + zeros)

            def one(s, pool):
                # a null pair moves nothing: skipping it keeps a
                # COW-free step from streaming S blocks onto themselves
                return jax.lax.cond(src[s] != dst[s], move,
                                    lambda s, pool: pool, s, pool)

            return jax.lax.fori_loop(0, src.shape[0], one, pool)

        new = {"k": copy(self.k), "v": copy(self.v)}
        if self.quantized:
            new["k_scale"] = copy(self.k_scale)
            new["v_scale"] = copy(self.v_scale)
        return dataclasses.replace(self, **new)


class StateSpec(NamedTuple):
    """What a state kind holds (a model's ``cache_kinds`` entry, in the
    place of a block kind's ``(layers, window)``): ``layers`` of the kind,
    each keeping a slot the conv's last ``conv_taps - 1`` inputs over
    ``conv_channels`` channels and an SSM state of ``heads * head_dim``
    channels by ``state_size`` float32. ``layout`` says which of the two is
    the state's LAST axis, the one that fills the lanes:

    - ``"state_last"``: ``(heads, head_dim, state_size)`` a slot (Mamba-2:
      a state of 128 is a whole tile's lanes);
    - ``"channels_last"``: ``(state_size, heads * head_dim)`` a slot
      (Mamba-1: a state of 16 last would pad 16 lanes to 128, eight times
      the bytes)."""

    layers: int
    conv_channels: int
    conv_taps: int
    heads: int
    head_dim: int
    state_size: int
    layout: str = "state_last"

    @property
    def slot_shape(self) -> Tuple[int, ...]:
        """One slot's SSM state at one layer."""
        if self.layout == "channels_last":
            return (self.state_size, self.heads * self.head_dim)
        if self.layout != "state_last":
            raise ValueError(f"state layout {self.layout!r}")
        return (self.heads, self.head_dim, self.state_size)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class SlotStateCache:
    """The per-slot recurrent state of one layer kind (module docstring, "A
    state kind"), in the layout its :class:`StateSpec` names. Both leaves
    end in an axis that fills the lanes, and neither pads its sublanes:

    - ``"state_last"``: ``conv`` ``(L, slots, taps - 1, channels)``
      (time-major a slot: ``(channels, 3)`` would pad 3 lanes to 128),
      ``ssm`` ``(L, slots, heads, head_dim, state)``;
    - ``"channels_last"``: ``conv`` ``(L, taps - 1, slots, channels)`` (the
      slots second to last: at 5,120 channels and 128 slots a ``(3,
      channels)`` tile a slot pads 3 rows to a tile's 16 and the compiler
      relays the whole array on its way into and out of every step),
      ``ssm`` ``(L, slots, state, channels)``.

    The model reads and writes a layer's tails slot-major through
    :meth:`tails` and :meth:`write_layer` whichever way they lie."""

    conv: jnp.ndarray
    ssm: jnp.ndarray           # (L, slots, *spec.slot_shape) float32
    layout: str = "state_last"

    def tree_flatten(self):
        return (self.conv, self.ssm), self.layout

    @classmethod
    def tree_unflatten(cls, layout, leaves):
        return cls(*leaves, layout=layout)

    @classmethod
    def create(cls, spec: StateSpec, max_seqs: int,
               dtype=jnp.bfloat16) -> "SlotStateCache":
        conv = (spec.layers, max_seqs, spec.conv_taps - 1,
                spec.conv_channels)
        if spec.layout == "channels_last":
            conv = (conv[0], conv[2], conv[1], conv[3])
        return cls(jnp.zeros(conv, dtype),
                   jnp.zeros((spec.layers, max_seqs) + spec.slot_shape,
                             jnp.float32), layout=spec.layout)

    @property
    def _slots_first(self) -> bool:
        return self.layout != "channels_last"

    def nbytes(self) -> int:
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in (self.conv, self.ssm))

    @property
    def bytes_per_slot(self) -> int:
        return self.nbytes() // self.ssm.shape[1]

    def tails(self, layer) -> jnp.ndarray:
        """The conv tails of every slot at ``layer``: ``(slots, taps - 1,
        channels)``."""
        rows = self.conv[layer]
        return rows if self._slots_first else jnp.swapaxes(rows, 0, 1)

    def write_slot(self, layer, slot, conv_tail, state) -> "SlotStateCache":
        """A prefill's result for ``slot`` at ``layer`` (int32 scalars):
        the rows are OVERWRITTEN, whatever the slot held. ``conv_tail``
        ``(taps - 1, channels)``."""
        tail = conv_tail.astype(self.conv.dtype)
        conv = self.conv
        if self._slots_first:
            conv = conv.at[layer, slot].set(tail)
        else:
            # a row a tap: ONE strided write of (taps - 1, channels) makes
            # the compiler hold the whole array taps-second-to-last through
            # the layer loop and relay it on the way in and out
            for tap in range(tail.shape[0]):
                conv = conv.at[layer, tap, slot].set(tail[tap])
        return dataclasses.replace(
            self, conv=conv,
            ssm=self.ssm.at[layer, slot].set(state.astype(jnp.float32)))

    def write_tails(self, layer, conv_tails) -> "SlotStateCache":
        """A decode step's conv tails of every slot at ``layer``,
        ``(slots, taps - 1, channels)`` (the caller keeps an idle slot's
        rows as they were): one in-place slab."""
        tails = conv_tails.astype(self.conv.dtype)
        if not self._slots_first:
            tails = jnp.swapaxes(tails, 0, 1)
        return dataclasses.replace(self,
                                   conv=self.conv.at[layer].set(tails))

    def write_layer(self, layer, conv_tails, states) -> "SlotStateCache":
        """:meth:`write_tails` and the SSM rows of every slot at ``layer``,
        one in-place slab a leaf (a mixer whose update writes the stacked
        state itself replaces ``ssm`` instead)."""
        return dataclasses.replace(
            self.write_tails(layer, conv_tails),
            ssm=self.ssm.at[layer].set(states.astype(jnp.float32)))


class LatentSpec(NamedTuple):
    """What a latent kind holds (a model's ``cache_kinds`` entry, module
    docstring "A latent kind"): ``layers`` of the kind, each caching a
    position's ``latent``-wide key-value latent and ``rope``-wide
    positional key."""

    layers: int
    latent: int
    rope: int


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class LatentPagedCache:
    """The block pool of a latent kind, a position's row in two leaves that
    each fill whole lane tiles, so that the compiler keeps both as the
    decode kernel reads them and nothing is relaid or padded:

    - ``latent`` ``(L, NB, block_size, latent)``, token-major like
      :class:`PagedKVCache`'s K and V (256 lanes at Mistral Small 4);
    - ``rope`` ``(L, NB, rope, block_size)``, the positions of a block on
      the lanes: a 64-wide last axis would half-fill a tile, and the
      compiler would then lay the whole pool with the block axis minor and
      relay it for the kernel on every step (module docstring).

    Both are carried through the layer scan as the KV pools are (a layer's
    rows written in place, the decode kernel taking the stacked pools and a
    layer index). Block 0 is the null block."""

    latent: jnp.ndarray
    rope: jnp.ndarray

    def tree_flatten(self):
        return (self.latent, self.rope), None

    @classmethod
    def tree_unflatten(cls, _, leaves):
        return cls(*leaves)

    @classmethod
    def create(cls, spec: LatentSpec, num_blocks: int, block_size: int,
               dtype=jnp.bfloat16) -> "LatentPagedCache":
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2 (block 0 is the "
                             f"reserved null block), got {num_blocks}")
        blocks = (spec.layers, num_blocks)
        return cls(jnp.zeros(blocks + (block_size, spec.latent), dtype),
                   jnp.zeros(blocks + (spec.rope, block_size), dtype))

    @property
    def block_size(self) -> int:
        return self.latent.shape[2]

    def nbytes(self) -> int:
        return sum(x.size * x.dtype.itemsize for x in (self.latent, self.rope))

    def append(self, layer, rows: jnp.ndarray, block_ids: jnp.ndarray,
               offsets: jnp.ndarray) -> "LatentPagedCache":
        """One row a slot at ``layer``, ``rows`` ``(S, latent + rope)``,
        where :meth:`PagedKVCache.append` would put it."""
        kr = self.latent.shape[-1]
        rows = rows.astype(self.latent.dtype)
        return LatentPagedCache(
            self.latent.at[layer, block_ids, offsets, :].set(
                rows[:, :kr], mode="drop"),
            self.rope.at[layer, block_ids, :, offsets].set(
                rows[:, kr:], mode="drop"))

    def write_layer_blocks(self, layer, rows: jnp.ndarray,
                           block_row: jnp.ndarray) -> "LatentPagedCache":
        """A prompt's rows ``(P, latent + rope)`` at ``layer``, whole
        blocks as :meth:`PagedKVCache.write_layer_blocks` lands them."""
        P, bs = rows.shape[0], self.block_size
        if P % bs:
            raise ValueError(f"prompt window {P} must be a multiple of "
                             f"block_size {bs}")
        kr = self.latent.shape[-1]
        blocks = rows.astype(self.latent.dtype).reshape(P // bs, bs, -1)
        row = jnp.asarray(block_row, jnp.int32)
        return LatentPagedCache(
            self.latent.at[layer, row].set(blocks[..., :kr], mode="drop"),
            self.rope.at[layer, row].set(
                blocks[..., kr:].transpose(0, 2, 1), mode="drop"))

    def scrub_null_block(self) -> "LatentPagedCache":
        return LatentPagedCache(self.latent.at[:, NULL_BLOCK].set(0),
                                self.rope.at[:, NULL_BLOCK].set(0))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KindPagedKVCache:
    """What a pattern model's layer kinds keep, by kind (module docstring,
    "Pools by layer kind", "A state kind"): ``pools[kind]`` is a
    :class:`PagedKVCache` ``(layers of the kind, num_blocks of the kind,
    block_size, H_kv * D)``, block 0 of each its own null block, a
    :class:`LatentPagedCache` or a :class:`SlotStateCache`."""

    pools: Dict[str, "PagedKVCache | LatentPagedCache | SlotStateCache"]

    def tree_flatten(self):
        kinds = tuple(sorted(self.pools))    # as jax orders a dict's keys
        return tuple(self.pools[k] for k in kinds), kinds

    @classmethod
    def tree_unflatten(cls, kinds, pools):
        return cls(dict(zip(kinds, pools)))

    @classmethod
    def create(cls, kinds: Dict[str, tuple],
               num_blocks: Dict[str, int], num_heads: int, block_size: int,
               head_dim: int, dtype=jnp.bfloat16,
               max_seqs: Optional[int] = None) -> "KindPagedKVCache":
        """``kinds``: ``{kind: (layers, window or None)}`` for a block kind,
        a :class:`LatentSpec` for a latent kind or a :class:`StateSpec` for
        a state kind, as the model's ``cfg.cache_kinds`` gives it;
        ``num_blocks[kind]`` (block and latent kinds) includes the kind's
        null block; ``max_seqs`` sizes the state kinds."""
        if jnp.dtype(dtype) == jnp.int8:
            raise ValueError("pools by layer kind are unquantized")
        pools = {}
        for kind, spec in kinds.items():
            if isinstance(spec, StateSpec):
                if max_seqs is None:
                    raise ValueError(f"state kind {kind!r} needs max_seqs")
                pools[kind] = SlotStateCache.create(spec, max_seqs, dtype)
            elif isinstance(spec, LatentSpec):
                pools[kind] = LatentPagedCache.create(
                    spec, num_blocks[kind], block_size, dtype)
            else:
                pools[kind] = PagedKVCache.create(
                    spec[0], num_blocks[kind], num_heads, block_size,
                    head_dim, dtype)
        return cls(pools)

    def nbytes(self) -> int:
        return sum(pool.nbytes() for pool in self.pools.values())

    @property
    def state_bytes_per_slot(self) -> int:
        return sum(pool.bytes_per_slot for pool in self.pools.values()
                   if isinstance(pool, SlotStateCache))

    def scrub_null_blocks(self) -> "KindPagedKVCache":
        def scrub(pool):
            if isinstance(pool, SlotStateCache):
                return pool
            if isinstance(pool, LatentPagedCache):
                return pool.scrub_null_block()
            return dataclasses.replace(pool, k=pool.k.at[:, NULL_BLOCK].set(0),
                                       v=pool.v.at[:, NULL_BLOCK].set(0))

        return KindPagedKVCache({kind: scrub(pool)
                                 for kind, pool in self.pools.items()})


# ---------------------------------------------------------------------------
# host-side block allocator: refcounts, prefix hashing, copy-on-write
# ---------------------------------------------------------------------------


class PoolExhausted(RuntimeError):
    """No allocatable pool block (free list empty, nothing evictable)."""


@dataclasses.dataclass
class AdmitPlan:
    """What :meth:`BlockAllocator.admit` decided for one admission.

    ``shared_tokens > 0`` means a prefix hit: the first
    ``shared_tokens`` positions are already in mapped (refcounted)
    shared blocks and the engine must run ONLY ``suffix`` through the
    decode program — the TTFT win. ``prefill=True`` is the cold path:
    run the full prefill program into ``block_row``."""

    slot: int
    prompt_len: int
    prefill: bool
    block_row: List[int]        # prefill destinations (cold path only)
    shared_tokens: int = 0
    suffix: Tuple[int, ...] = ()
    cow_pending: bool = False   # the last shared block awaits COW


@dataclasses.dataclass
class StepPlan:
    """Per-decode-step device arguments from
    :meth:`BlockAllocator.prepare_step`: the COW copy pairs (null
    no-ops when nothing is pending) and the slots that could NOT be
    given a block to write (pool exhausted) — the scheduler retires
    those loudly instead of letting a write silently drop."""

    cow_src: np.ndarray         # (S,) int32
    cow_dst: np.ndarray         # (S,) int32
    failed: List[int]


class BlockAllocator:
    """Host-side bookkeeping for a :class:`PagedKVCache` (see the module
    docstring): the free list, per-block refcounts, per-slot block
    tables + cursors (the mirrors threaded into the AOT programs), the
    chained prefix-hash index, and lazily-resolved copy-on-write.

    Prefix sharing: a COLD admission registers each FULL prompt block
    under a chained hash (block i's key digests block i-1's key plus
    the chunk's tokens, so a hit at depth i certifies the whole prefix).
    A later admission walks the chain; hits map the shared blocks into
    its table (refcount++) and skip prefill for the shared span. Hash
    collisions cannot serve wrong KV: every index entry stores its
    exact token chunk and a mismatch falls back to the cold path
    (tested in ``tests/test_paged.py``). Retired blocks whose content
    is still registered park in an LRU "cached" pool (refcount 0, not
    yet freed) so a follow-up admission with the same prefix still
    hits; allocation pressure evicts them oldest-first.

    Copy-on-write: when a hit covers the WHOLE prompt, the admission
    maps the final shared block but must write its own KV into it (the
    last prompt position belongs to this request's divergence point) —
    the block is marked COW-pending and the next
    :meth:`prepare_step` that sees the slot's cursor inside it
    allocates a private copy target; the device copies before it
    writes. Writes into fully-shared spans never happen (appends past
    the shared span land in freshly-owned blocks), so this lazy single
    pending block is the complete COW story."""

    def __init__(self, num_blocks: int, block_size: int,
                 blocks_per_slot: int, max_seqs: int,
                 window: Optional[int] = None):
        if num_blocks < 2:
            raise ValueError(f"num_blocks must be >= 2, got {num_blocks}")
        if window is not None and window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.blocks_per_slot = int(blocks_per_slot)
        self.max_seqs = int(max_seqs)
        # a window layer's pool: the row at cursor c reads positions
        # > c - window, so a block wholly at or below c - window is out
        # for good (the cursor only grows) and goes back to the pool
        self.window = None if window is None else int(window)
        self.blocks_given = 0        # monotonic: blocks ever mapped
        self.blocks_returned = 0     # ... and handed back by the window
        # per slot, the first table index the window has not handed back
        self._live_from = np.zeros(max_seqs, np.int32)
        # LIFO free list; block 0 is the reserved null block
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self.refcount = np.zeros(num_blocks, np.int32)
        self.refcount[NULL_BLOCK] = 1           # pinned forever
        self.tables = np.zeros((max_seqs, blocks_per_slot), np.int32)
        self.lengths = np.zeros(max_seqs, np.int32)
        # prefix index: chain digest -> (block, parent digest, chunk)
        self._index: Dict[bytes, Tuple[int, Optional[bytes],
                                       Tuple[int, ...]]] = {}
        self._block_key: Dict[int, bytes] = {}
        # refcount-0 blocks still registered: evictable LRU
        self._cached: "collections.OrderedDict[int, None]" = \
            collections.OrderedDict()
        self._cow_pending: Dict[int, int] = {}   # slot -> table index
        # monotonic host counters the scheduler snapshots into serve/*
        self.cow_copies = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0

    # -- capacity -----------------------------------------------------------

    @property
    def free_blocks(self) -> int:
        """Immediately allocatable blocks (free + evictable cached)."""
        return len(self._free) + len(self._cached)

    @property
    def capacity_tokens(self) -> int:
        """Per-slot token capacity (the table width in tokens)."""
        return self.blocks_per_slot * self.block_size

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks a cold admission of ``n_tokens`` maps: all of them, or
        under a window those the row at the cursor still reads."""
        return (-(-int(n_tokens) // self.block_size)
                - self.first_live_block(n_tokens))

    def can_admit(self, n_tokens: int) -> bool:
        """Whether a cold admission of ``n_tokens`` finds its blocks."""
        return self.free_blocks >= self.blocks_for(n_tokens)

    def first_live_block(self, cursor: int) -> int:
        """The first block the row at ``cursor`` (and every later row)
        can still read: 0 without a window."""
        if self.window is None:
            return 0
        return max(0, int(cursor) - self.window + 1) // self.block_size

    def trim(self, slot: int) -> int:
        """Hand back ``slot``'s blocks that lie wholly left of its
        window; how many went. No-op without a window."""
        if self.window is None:
            return 0
        gone = 0
        live = self.first_live_block(self.lengths[slot])
        for bidx in range(int(self._live_from[slot]), live):
            block = int(self.tables[slot, bidx])
            if block != NULL_BLOCK:
                self.tables[slot, bidx] = NULL_BLOCK
                self._release_block(block)
                gone += 1
        self._live_from[slot] = max(int(self._live_from[slot]), live)
        self.blocks_returned += gone
        return gone

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - 1 - self.free_blocks

    def walk_blocks(self) -> Tuple[int, int]:
        """``(live, table)``: the blocks that hold cached positions some
        slot's next row reads (what the paged kernel walks, ``ops.
        flash_attention.paged_work_list``), and ``max_seqs x
        blocks_per_slot``, the table's capacity."""
        cur = self.lengths.astype(np.int64)
        first = 0 if self.window is None else \
            np.maximum(cur - self.window + 1, 0) // self.block_size
        return (int(np.sum(-(-cur // self.block_size) - first)),
                self.max_seqs * self.blocks_per_slot)

    # -- low-level block lifecycle ------------------------------------------

    def _evict_one(self) -> int:
        block, _ = self._cached.popitem(last=False)   # oldest first
        self._unregister(block)
        return block

    def _take_block(self) -> int:
        if self._free:
            return self._free.pop()
        if self._cached:
            return self._evict_one()
        raise PoolExhausted(
            f"block pool exhausted: {self.num_blocks - 1} allocatable "
            "blocks all referenced")

    def _unregister(self, block: int) -> None:
        key = self._block_key.pop(block, None)
        if key is not None and self._index.get(key, (None,))[0] == block:
            del self._index[key]

    def _release_block(self, block: int) -> None:
        if block == NULL_BLOCK:
            return
        self.refcount[block] -= 1
        if self.refcount[block] > 0:
            return
        if block in self._block_key:
            # content still registered: park it for prefix reuse
            self._cached[block] = None
        else:
            self._free.append(block)

    def _revive(self, block: int) -> None:
        """refcount 0 -> 1 on a cached (registered, unowned) block."""
        if self.refcount[block] == 0:
            self._cached.pop(block, None)
        self.refcount[block] += 1

    # -- prefix hashing ------------------------------------------------------

    @staticmethod
    def _digest(parent: Optional[bytes],
                chunk: Sequence[int]) -> bytes:
        h = hashlib.sha256(parent or b"")
        h.update(np.asarray(chunk, np.int64).tobytes())
        return h.digest()

    def _chain(self, prompt: Sequence[int]):
        """(digest, chunk) per FULL block of ``prompt``, chained."""
        bs = self.block_size
        out = []
        parent: Optional[bytes] = None
        for i in range(len(prompt) // bs):
            chunk = tuple(int(t) for t in prompt[i * bs:(i + 1) * bs])
            digest = self._digest(parent, chunk)
            out.append((digest, chunk))
            parent = digest
        return out

    def lookup(self, prompt: Sequence[int]) -> List[int]:
        """Longest verified chain of live shared blocks covering
        ``prompt``'s full-block prefix. Verification compares the STORED
        token chunk, so a digest collision reads as a miss (falls back
        to full prefill — never serves wrong KV)."""
        blocks: List[int] = []
        for digest, chunk in self._chain(prompt):
            entry = self._index.get(digest)
            if entry is None or entry[2] != chunk:
                break
            blocks.append(entry[0])
        return blocks

    # -- admission / registration / release ---------------------------------

    def admit(self, slot: int, prompt: Sequence[int],
              prefill_blocks: int, share: bool = True) -> AdmitPlan:
        """Map ``slot``'s table for ``prompt`` and return the plan.

        ``prefill_blocks`` is the engine's static prompt window in
        blocks — the cold path allocates only ``ceil(P/block_size)``
        real blocks and pads the row with nulls. ``share=False`` forces
        the cold path even on a prefix hit (the engine's
        ``prefix_suffix_cap`` policy). Raises :class:`PoolExhausted`
        when the blocks aren't there (admission control queues on
        that); every partial allocation is rolled back first."""
        P = len(prompt)
        if not 0 <= slot < self.max_seqs:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.max_seqs})")
        if P > self.capacity_tokens:
            raise ValueError(f"prompt length {P} exceeds the per-slot "
                             f"capacity {self.capacity_tokens}")
        if np.any(self.tables[slot] != NULL_BLOCK) or self.lengths[slot]:
            raise ValueError(f"slot {slot} still holds blocks — release "
                             "it before re-admitting")
        shared = self.lookup(prompt) if share else []
        if shared:
            n_shared = len(shared)
            covers_all = n_shared * self.block_size >= P
            # the LAST prompt position is this request's divergence
            # point: it must be decoded (it samples the first token)
            # and its KV written — never shared
            shared_tokens = (P - 1 if covers_all
                             else n_shared * self.block_size)
            for b in shared:
                self._revive(b)
            self.tables[slot, :n_shared] = shared
            self.lengths[slot] = shared_tokens
            if covers_all:
                # the write at P-1 lands INSIDE the final shared block:
                # copy-on-write, resolved lazily at the next step
                self._cow_pending[slot] = n_shared - 1
            self.prefix_hits += 1
            self.prefix_hit_tokens += int(shared_tokens)
            return AdmitPlan(slot, P, prefill=False, block_row=[],
                             shared_tokens=int(shared_tokens),
                             suffix=tuple(int(t)
                                          for t in prompt[shared_tokens:]),
                             cow_pending=covers_all)
        # cold path: real blocks for the prompt, nulls for the padding
        # (and, under a window, for the blocks already left of it)
        n_skip = self.first_live_block(P)
        n_real = self.blocks_for(P)
        row: List[int] = []
        try:
            for _ in range(n_real):
                row.append(self._take_block())
        except PoolExhausted:
            for b in row:
                self._free.append(b)
            raise
        for b in row:
            self.refcount[b] = 1
        self.blocks_given += n_real
        self.tables[slot, n_skip:n_skip + n_real] = row
        self.lengths[slot] = P
        self._live_from[slot] = n_skip
        return AdmitPlan(slot, P, prefill=True,
                         block_row=[NULL_BLOCK] * n_skip + row
                         + [NULL_BLOCK] * (prefill_blocks - n_skip
                                           - n_real))

    def register_prefix(self, slot: int, prompt: Sequence[int]) -> None:
        """After a COLD prefill lands: index ``slot``'s full prompt
        blocks under their chain digests so later admissions can share
        them. Existing registrations win (their block is already
        shared-ready); a block never re-registers under a second key."""
        if self.window is not None:
            return      # a window hands its early blocks back: no sharing
        for i, (digest, chunk) in enumerate(self._chain(prompt)):
            block = int(self.tables[slot, i])
            if block == NULL_BLOCK or block in self._block_key:
                continue
            if digest in self._index:
                continue
            self._index[digest] = (block, None, chunk)
            self._block_key[block] = digest

    def release(self, slot: int) -> None:
        """Retire ``slot``: every mapped block drops a reference
        (registered blocks park in the prefix cache at refcount 0,
        unregistered ones free immediately); table and cursor zero."""
        for b in self.tables[slot]:
            self._release_block(int(b))
        self.tables[slot] = NULL_BLOCK
        self.lengths[slot] = 0
        self._live_from[slot] = 0
        self._cow_pending.pop(slot, None)

    # -- per-step device arguments ------------------------------------------

    def append_targets(self, active: np.ndarray):
        """``(block_ids, offsets)`` ``(S,)`` int32 for this step's
        append: each ACTIVE slot writes at its cursor; inactive or
        saturated slots aim at the null block."""
        cur = self.lengths
        bidx = np.minimum(cur // self.block_size,
                          self.blocks_per_slot - 1)
        bid = self.tables[np.arange(self.max_seqs), bidx].copy()
        ok = np.asarray(active, bool) & (cur < self.capacity_tokens)
        bid[~ok] = NULL_BLOCK
        return bid.astype(np.int32), (cur % self.block_size).astype(
            np.int32)

    def verify_targets(self, active: np.ndarray, k: int):
        """``(block_ids, offsets)`` ``(S, k)`` int32 for a k-token
        verify append: ACTIVE slot ``s`` writes token ``i`` at cursor
        position ``cursor + i`` — a window that may cross a block
        boundary, so each token names its own (block, offset) pair.
        Inactive slots and positions past capacity aim at the null
        block. :meth:`prepare_verify` must have mapped the touched
        blocks first."""
        cur = self.lengths[:, None].astype(np.int64)
        pos = cur + np.arange(k)[None, :]                       # (S, k)
        bidx = np.minimum(pos // self.block_size,
                          self.blocks_per_slot - 1)
        bid = np.take_along_axis(self.tables, bidx.astype(np.intp),
                                 axis=1).copy()
        ok = np.asarray(active, bool)[:, None] & \
            (pos < self.capacity_tokens)
        bid[~ok] = NULL_BLOCK
        return bid.astype(np.int32), (pos % self.block_size).astype(
            np.int32)

    def prepare_step(self, active_slots: Sequence[int]) -> StepPlan:
        """Make every active slot writable for ONE append: resolve any
        COW whose block the cursor is about to enter (allocate the
        private copy, swap the table entry, emit the device copy pair)
        and allocate a fresh block where the cursor crossed into an
        unmapped table entry. Slots the pool cannot serve land in
        ``failed`` — the scheduler retires them loudly."""
        return self.prepare_verify(active_slots, 1)

    def prepare_verify(self, active_slots: Sequence[int],
                       k: int) -> StepPlan:
        """:meth:`prepare_step` generalized to a k-token verify window:
        every block the window ``[cursor, cursor + k)`` touches — up to
        ``ceil(k/block_size) + 1`` table entries — is made slot-private
        and writable BEFORE the step: the cursor block's pending COW is
        resolved (rejected drafts must never scribble a shared block)
        and unmapped entries get fresh blocks. Allocation is atomic per
        slot: a slot the pool cannot fully serve rolls its partial
        grab back and lands in ``failed``. Blocks mapped for rows the
        verify then REJECTS stay mapped — they sit above the advanced
        cursor and the next window reuses them; release() frees them
        with the rest of the row."""
        cow_src = np.zeros(self.max_seqs, np.int32)
        cow_dst = np.zeros(self.max_seqs, np.int32)
        failed: List[int] = []
        for slot in active_slots:
            cur = int(self.lengths[slot])
            if cur >= self.capacity_tokens:
                failed.append(slot)
                continue
            first = cur // self.block_size
            last = min((cur + k - 1) // self.block_size,
                       self.blocks_per_slot - 1)
            pend = self._cow_pending.get(slot)
            if pend is not None and pend == first:
                old = int(self.tables[slot, first])
                try:
                    new = self._take_block()
                except PoolExhausted:
                    failed.append(slot)
                    continue
                self.refcount[new] = 1
                self.tables[slot, first] = new
                cow_src[slot] = old
                cow_dst[slot] = new
                # the device copies old -> new THIS step before any
                # write; dropping the reference now is safe because the
                # content survives in the still-live readers' mapping
                self._release_block(old)
                del self._cow_pending[slot]
                self.cow_copies += 1
            taken: List[int] = []
            short = False
            for bidx in range(first, last + 1):
                if self.tables[slot, bidx] != NULL_BLOCK:
                    continue
                try:
                    new = self._take_block()
                except PoolExhausted:
                    short = True
                    break
                self.refcount[new] = 1
                self.tables[slot, bidx] = new
                taken.append(bidx)
            if not short:
                self.blocks_given += len(taken)
            if short:
                # atomic per slot: hand the partial grab back so a
                # sibling slot (or the next step) can use it
                for bidx in taken:
                    b = int(self.tables[slot, bidx])
                    self.tables[slot, bidx] = NULL_BLOCK
                    self._release_block(b)
                failed.append(slot)
        return StepPlan(cow_src, cow_dst, failed)

    def advance(self, slots: Sequence[int]) -> None:
        """Cursor mirror +1 for the slots whose append just landed."""
        for slot in slots:
            self.lengths[slot] = min(int(self.lengths[slot]) + 1,
                                     self.capacity_tokens)
            if self.window is not None:
                self.trim(slot)

    def advance_counts(self, slots: Sequence[int],
                       counts: Sequence[int]) -> None:
        """Cursor mirror advance by each slot's ACCEPTED verify count —
        the rejected tail of the window stays above the cursor, invisible
        to every read."""
        for slot, n in zip(slots, counts):
            self.lengths[slot] = min(int(self.lengths[slot]) + int(n),
                                     self.capacity_tokens)


class KindBlockAllocator:
    """The allocators of a pattern model's pools, one
    :class:`BlockAllocator` a layer kind, behind the calls the paged
    engine makes of one (module docstring, "Pools by layer kind"). What
    is an array there is a dict by kind here (``tables``, the block ids of
    ``append_targets``, an admission's ``block_row``); the cursor
    (``lengths``) is one a slot, the same in every kind. An admission or a
    step either gets its blocks in every kind or in none. No prefix is
    shared (a window layer has handed its early blocks back), so no
    copy-on-write is ever pending. A latent kind (:class:`LatentSpec`) is a
    block kind without a window. A state kind (:class:`StateSpec`) gets
    no allocator, no blocks and no table: its address is the slot. What is
    counted of it is how many slots hold one (:attr:`state_slots_in_use`:
    admitted and not yet released)."""

    def __init__(self, kinds: Dict[str, tuple],
                 num_blocks: Dict[str, int], block_size: int,
                 blocks_per_slot: int, max_seqs: int):
        self.kinds = {kind: BlockAllocator(
            num_blocks[kind], block_size, blocks_per_slot, max_seqs,
            window=None if isinstance(spec, LatentSpec) else spec[1])
            for kind, spec in kinds.items()
            if not isinstance(spec, StateSpec)}
        if not self.kinds:
            raise ValueError("a model with no block kind has no cursor: "
                             "the paged engine serves at least one")
        self.has_state = len(self.kinds) < len(kinds)
        self._held = np.zeros(max_seqs, bool)
        self._first = next(iter(self.kinds.values()))
        self.block_size = int(block_size)
        self.blocks_per_slot = int(blocks_per_slot)
        self.max_seqs = int(max_seqs)
        self.num_blocks = sum(num_blocks[kind] for kind in self.kinds)
        self.cow_copies = 0

    # -- what the engine threads into the programs ----------------------------

    @property
    def tables(self) -> Dict[str, np.ndarray]:
        return {kind: a.tables for kind, a in self.kinds.items()}

    @property
    def lengths(self) -> np.ndarray:
        return self._first.lengths

    @property
    def capacity_tokens(self) -> int:
        return self._first.capacity_tokens

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks over all kinds (the scheduler's gauge)."""
        return sum(a.free_blocks for a in self.kinds.values())

    def blocks_for(self, n_tokens: int) -> int:
        return sum(a.blocks_for(n_tokens) for a in self.kinds.values())

    def can_admit(self, n_tokens: int) -> bool:
        return all(a.can_admit(n_tokens) for a in self.kinds.values())

    def lookup(self, prompt) -> List[int]:
        return []

    def register_prefix(self, slot: int, prompt) -> None:
        """Nothing is indexed: see the class docstring."""

    # -- admission / stepping / release ----------------------------------------

    def admit(self, slot: int, prompt: Sequence[int], prefill_blocks: int,
              share: bool = False) -> AdmitPlan:
        rows, done = {}, []
        try:
            for kind, a in self.kinds.items():
                rows[kind] = a.admit(slot, prompt, prefill_blocks,
                                     share=False).block_row
                done.append(a)
        except Exception:
            for a in done:
                a.release(slot)
            raise
        self._held[slot] = True
        return AdmitPlan(slot, len(prompt), prefill=True, block_row=rows)

    def prepare_step(self, active_slots: Sequence[int]) -> StepPlan:
        failed: List[int] = []
        for a in self.kinds.values():
            failed += a.prepare_step(active_slots).failed
        zeros = np.zeros(self.max_seqs, np.int32)
        return StepPlan(zeros, zeros, sorted(set(failed)))

    def append_targets(self, active: np.ndarray):
        out = {kind: a.append_targets(active)
               for kind, a in self.kinds.items()}
        offsets = next(iter(out.values()))[1]
        return {kind: ids for kind, (ids, _) in out.items()}, offsets

    def advance(self, slots: Sequence[int]) -> None:
        for a in self.kinds.values():
            a.advance(slots)

    def release(self, slot: int) -> None:
        for a in self.kinds.values():
            a.release(slot)
        self._held[slot] = False

    # -- counters ----------------------------------------------------------------

    @property
    def state_slots_in_use(self) -> int:
        """Slots whose state rows belong to a request (0 where the model
        has no state kind)."""
        return int(self._held.sum()) if self.has_state else 0

    @property
    def blocks_in_use(self) -> Dict[str, int]:
        return {kind: a.blocks_in_use for kind, a in self.kinds.items()}

    def walk_blocks(self) -> Tuple[int, int]:
        """:meth:`BlockAllocator.walk_blocks`, summed over the kinds."""
        walks = [a.walk_blocks() for a in self.kinds.values()]
        return sum(w[0] for w in walks), sum(w[1] for w in walks)

    def window_blocks(self) -> Tuple[int, int]:
        """``(given, returned)``: blocks ever mapped by the window kinds
        and blocks their windows handed back before release."""
        win = [a for a in self.kinds.values() if a.window is not None]
        return (sum(a.blocks_given for a in win),
                sum(a.blocks_returned for a in win))

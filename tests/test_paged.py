"""Paged serving path (docs/SERVING.md "Paged serving"): the bounded
paged decode kernel vs the cache oracle, PagedKVCache pool writes, the
block allocator's refcount/COW/prefix-hash lifecycle, and the engine's
contracts over a pool smaller than the default — prefill+decode parity
vs the one-shot forward, prefix-shared stream identity, zero-recompile
across admit/COW/retire, pool-exhaustion admission control — and over
the default one, sized so that every slot can reach ``max_len``."""

import importlib
import os

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.ops.flash_attention import (mha_reference,
                                          paged_decode_attention)
from apex_tpu.serving import (BlockAllocator, PagedKVCache,
                              PagedServingEngine, PoolExhausted, Rejection,
                              Request, ServingEngine, SlotScheduler,
                              paged_block_bytes)

from _program_text import program_text

# the package re-exports the FUNCTION under the module's name
fa = importlib.import_module("apex_tpu.ops.flash_attention")


def _quantize_ref(x):
    scale = np.maximum(np.abs(x).max(-1) / 127.0, 1e-8)
    q = np.clip(np.round(x / scale[..., None]), -127, 127).astype(np.int8)
    return q, scale.astype(np.float32)


# ---------------------------------------------------------------------------
# the paged decode kernel vs the mha_reference cache oracle
# ---------------------------------------------------------------------------

class TestPagedDecodeKernel:
    B, H, BS, NBS, D = 4, 4, 32, 8, 32      # per-slot span 256
    NB = 34                                  # pool blocks (0 = null)
    L, LAYER = 2, 1                          # the kernel reads ONE layer
    LENGTHS = [0, 1, 100, 256]               # empty, single, partial, full

    def _layout(self, rng):
        """Random pool layout: each slot's blocks scattered through the
        pool (never block 0), plus the dense gather for the oracle."""
        perm = rng.permutation(np.arange(1, self.NB))
        tables = perm[: self.B * self.NBS].reshape(self.B, self.NBS)
        return tables.astype(np.int32)

    def _pool(self, rng):
        """A token-major stacked pool ``(L, NB, BS, H*D)``."""
        return rng.randn(self.L, self.NB, self.BS,
                         self.H * self.D).astype(np.float32)

    def _dense_of(self, pool, tables):
        """Layer ``LAYER``'s table-mapped blocks as ``(B, H, T, D)``."""
        g = np.asarray(pool, np.float32)[self.LAYER][tables]
        return g.reshape(self.B, self.NBS * self.BS, self.H,
                         self.D).transpose(0, 2, 1, 3)

    @pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                           (jnp.bfloat16, 2e-2)])
    def test_parity_vs_cache_oracle(self, dtype, tol):
        rng = np.random.RandomState(0)
        tables = self._layout(rng)
        lengths = jnp.asarray(self.LENGTHS, jnp.int32)
        q = jnp.asarray(rng.randn(self.B, self.H, self.D), dtype)
        kp = jnp.asarray(self._pool(rng), dtype)
        vp = jnp.asarray(self._pool(rng), dtype)
        k_new = jnp.asarray(rng.randn(self.B, self.H, self.D), dtype)
        v_new = jnp.asarray(rng.randn(self.B, self.H, self.D), dtype)
        out = paged_decode_attention(q, kp, vp, self.LAYER,
                                     jnp.asarray(tables), lengths,
                                     k_new=k_new, v_new=v_new)
        # oracle: dense-gather the pool and write the current token at
        # each row's CURSOR (kv_length masks everything past it)
        kd = np.concatenate([self._dense_of(kp, tables),
                             np.zeros((self.B, self.H, 1, self.D),
                                      np.float32)], axis=2)
        vd = np.concatenate([self._dense_of(vp, tables),
                             np.zeros((self.B, self.H, 1, self.D),
                                      np.float32)], axis=2)
        for i, ln in enumerate(self.LENGTHS):
            kd[i, :, ln] = np.asarray(k_new, np.float32)[i]
            vd[i, :, ln] = np.asarray(v_new, np.float32)[i]
        ref = mha_reference(
            q[:, :, None].astype(jnp.float32), jnp.asarray(kd),
            jnp.asarray(vd), kv_length=lengths + 1)[:, :, 0]
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32), atol=tol)

    def _quantized_pool(self, rng):
        """int8 pool + its ``(L, NB, H, BS)`` scale plane + the
        dequantized float image, per-(position, head) scales."""
        f = self._pool(rng).reshape(self.L, self.NB, self.BS, self.H,
                                    self.D)
        q, sc = _quantize_ref(f)                 # sc (L, NB, BS, H)
        deq = (q.astype(np.float32) * sc[..., None]).reshape(
            self.L, self.NB, self.BS, self.H * self.D)
        return (q.reshape(deq.shape), sc.transpose(0, 1, 3, 2).copy(),
                deq)

    def test_parity_int8(self):
        rng = np.random.RandomState(1)
        tables = self._layout(rng)
        lengths = jnp.asarray(self.LENGTHS, jnp.int32)
        q = jnp.asarray(rng.randn(self.B, self.H, self.D), jnp.float32)
        kq, ksc, kdeq = self._quantized_pool(rng)
        vq, vsc, vdeq = self._quantized_pool(rng)
        out = paged_decode_attention(
            q, jnp.asarray(kq), jnp.asarray(vq), self.LAYER,
            jnp.asarray(tables), lengths, k_scale=jnp.asarray(ksc),
            v_scale=jnp.asarray(vsc))
        ref = mha_reference(q[:, :, None],
                            jnp.asarray(self._dense_of(kdeq, tables)),
                            jnp.asarray(self._dense_of(vdeq, tables)),
                            kv_length=lengths)[:, :, 0]
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-2)

    def test_pallas_matches_xla_fallback(self):
        rng = np.random.RandomState(2)
        tables = self._layout(rng)
        lengths = jnp.asarray([7, 63, 128, 200], jnp.int32)
        q = jnp.asarray(rng.randn(self.B, self.H, self.D), jnp.float32)
        kp = jnp.asarray(self._pool(rng))
        vp = jnp.asarray(self._pool(rng))
        a = paged_decode_attention(q, kp, vp, self.LAYER,
                                   jnp.asarray(tables), lengths,
                                   use_pallas=True)
        b = paged_decode_attention(q, kp, vp, self.LAYER,
                                   jnp.asarray(tables), lengths,
                                   use_pallas=False)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)

    def test_current_token_merge_matches_in_cache_oracle(self):
        """paged_decode_attention(k_new=...) over an L-length prefix must
        equal the oracle over an (L+1)-length cache with the token
        written at the cursor — the exactness the write-after-read decode
        step relies on — on the kernel and on its XLA fallback; and with
        an empty prefix it is a softmax over one position: exactly
        ``v_new``."""
        rng = np.random.RandomState(4)
        tables = self._layout(rng)
        prefix = [0, 1, 100, 255]        # 255: the last position is free
        q = jnp.asarray(rng.randn(self.B, self.H, self.D), jnp.float32)
        kp, vp = self._pool(rng), self._pool(rng)
        kn = rng.randn(self.B, self.H, self.D).astype(np.float32)
        vn = rng.randn(self.B, self.H, self.D).astype(np.float32)
        kd, vd = self._dense_of(kp, tables), self._dense_of(vp, tables)
        for i, ln in enumerate(prefix):
            kd[i, :, ln], vd[i, :, ln] = kn[i], vn[i]
        ref = mha_reference(q[:, :, None], jnp.asarray(kd), jnp.asarray(vd),
                            kv_length=jnp.asarray(prefix) + 1)[:, :, 0]
        for use_pallas in (True, False):
            out = paged_decode_attention(
                q, jnp.asarray(kp), jnp.asarray(vp), self.LAYER,
                jnp.asarray(tables), jnp.asarray(prefix, jnp.int32),
                k_new=jnp.asarray(kn), v_new=jnp.asarray(vn),
                use_pallas=use_pallas)
            np.testing.assert_allclose(out, ref, atol=2e-6)
            np.testing.assert_array_equal(np.asarray(out[0]), vn[0])

    def test_int8_requires_scales(self):
        z8 = jnp.zeros((1, 2, 8, 16), jnp.int8)
        with pytest.raises(ValueError, match="k_scale"):
            paged_decode_attention(jnp.zeros((1, 2, 8)), z8, z8, 0,
                                   jnp.zeros((1, 1), jnp.int32),
                                   jnp.zeros(1, jnp.int32))

    def test_forced_pallas_on_a_pool_off_the_lanes_is_served(self):
        """No shape is refused: every block of the kernel spans its
        array's last two dims whole, so ``use_pallas=True`` on a pool
        whose ``H * D`` (120) is no multiple of the 128 lanes, in blocks
        of 8 tokens, reads what the oracle reads (Mosaic takes the same
        shape: ``tests/test_chip_compile.py``, ``paged_decode_off_lanes``).
        A cursor short of its last block's end must not read that
        block's tail."""
        rng = np.random.RandomState(5)
        B, H, D, BS, NBS = 2, 3, 40, 8, 3
        tables = np.asarray([[4, 1, 5], [2, 6, 3]], np.int32)
        lengths = jnp.asarray([BS * NBS, BS + 3], jnp.int32)
        q = jnp.asarray(rng.randn(B, H, D), jnp.float32)
        kp = rng.randn(1, 7, BS, H * D).astype(np.float32)
        vp = rng.randn(1, 7, BS, H * D).astype(np.float32)

        def dense(pool):
            return jnp.asarray(pool[0][tables].reshape(
                B, NBS * BS, H, D).transpose(0, 2, 1, 3))

        out = paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                     0, jnp.asarray(tables), lengths,
                                     use_pallas=True)
        ref = mha_reference(q[:, :, None], dense(kp), dense(vp),
                            kv_length=lengths)[:, :, 0]
        np.testing.assert_allclose(out, ref, atol=2e-6)

    def test_unmapped_tail_blocks_never_pollute(self):
        """Table entries past ceil(length/block) may be garbage (null or
        stale) — the clamped index map / length mask must keep them out
        of the math, and so must the layer index the other layers."""
        rng = np.random.RandomState(3)
        tables = self._layout(rng)
        lengths = jnp.asarray([40, 40, 40, 40], jnp.int32)  # 2 blocks
        q = jnp.asarray(rng.randn(self.B, self.H, self.D), jnp.float32)
        kp, vp = self._pool(rng), self._pool(rng)
        out1 = paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                      self.LAYER, jnp.asarray(tables),
                                      lengths)
        # poison every block the cursor doesn't cover, and layer 0 whole
        used = set(tables[:, :2].ravel().tolist())
        for blk in range(self.NB):
            if blk not in used:
                kp[self.LAYER, blk] = 1e6
                vp[self.LAYER, blk] = 1e6
        kp[0] = vp[0] = 1e6
        out2 = paged_decode_attention(q, jnp.asarray(kp), jnp.asarray(vp),
                                      self.LAYER, jnp.asarray(tables),
                                      lengths)
        np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                                   atol=1e-6)


class TestPagedDecodeKernelLargeWidths:
    """The serve cell's head geometry (gpt2-large: 20 heads x 64, block
    128): every head of a block is scored at once through the
    block-diagonal query, so parity is checked where 20 heads share one
    1280-lane row — cursors inside a block, on a block boundary, at 0."""
    B, H, BS, NBS, D = 3, 20, 128, 2, 64
    NB, L, LAYER = 8, 2, 1
    LENGTHS = [0, 128, 200]                  # empty, boundary, inside

    def _case(self, rng, q_len, quantized):
        tables = rng.permutation(np.arange(1, self.NB))[
            : self.B * self.NBS].reshape(self.B, self.NBS).astype(np.int32)
        qs = (self.B, self.H, self.D) if q_len == 1 else \
            (self.B, self.H, q_len, self.D)
        q = rng.randn(*qs).astype(np.float32)

        def pool():
            f = rng.randn(self.L, self.NB, self.BS, self.H,
                          self.D).astype(np.float32)
            flat = (self.L, self.NB, self.BS, self.H * self.D)
            if not quantized:
                bf = np.asarray(jnp.asarray(f, jnp.bfloat16), np.float32)
                return jnp.asarray(f.reshape(flat), jnp.bfloat16), None, bf
            qv, sc = _quantize_ref(f)
            return (jnp.asarray(qv.reshape(flat)),
                    jnp.asarray(sc.transpose(0, 1, 3, 2).copy()),
                    qv.astype(np.float32) * sc[..., None])

        def dense(image):                    # (L,NB,BS,H,D) -> (B,H,T,D)
            g = image[self.LAYER][tables]    # (B, NBS, BS, H, D)
            return jnp.asarray(g.reshape(
                self.B, self.NBS * self.BS, self.H,
                self.D).transpose(0, 2, 1, 3))

        kp, ksc, kimg = pool()
        vp, vsc, vimg = pool()
        return q, tables, (kp, vp, ksc, vsc), dense(kimg), dense(vimg)

    @pytest.mark.parametrize("q_len", [1, 5])
    @pytest.mark.parametrize("quantized", [False, True],
                             ids=["bf16", "int8"])
    def test_parity_vs_mha_reference(self, q_len, quantized):
        rng = np.random.RandomState(10 * q_len + quantized)
        q, tables, (kp, vp, ksc, vsc), kd, vd = self._case(
            rng, q_len, quantized)
        lengths = jnp.asarray(self.LENGTHS, jnp.int32)
        out = paged_decode_attention(
            jnp.asarray(q), kp, vp, self.LAYER, jnp.asarray(tables),
            lengths, k_scale=ksc, v_scale=vsc)
        q4 = jnp.asarray(q if q_len > 1 else q[:, :, None])
        ref = mha_reference(q4, kd, vd, kv_length=lengths)
        if q_len == 1:
            ref = ref[:, :, 0]
        assert out.shape == q.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        # the empty slot reads exactly zero, whatever its table names
        assert not np.any(np.asarray(out)[0])


# ---------------------------------------------------------------------------
# PagedKVCache pool writes
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# the walk of the live blocks (PR 36): the kernel's grid is the work list
# ---------------------------------------------------------------------------

WALK_BS, WALK_NBS, WALK_D, WALK_LAYER = 8, 4, 8, 1   # a slot spans 32
# name: (query heads, KV heads, q_len, window, int8, cursors)
WALK_CASES = {
    # 0, 1, block_size, block_size + 1, the table's full span, mid-block
    "ragged": (2, 2, 1, None, False, [0, 1, 8, 9, 32, 20]),
    "all_empty": (2, 2, 1, None, False, [0, 0, 0, 0]),
    "one_live_of_32": (2, 2, 1, None, False, [0] * 17 + [19] + [0] * 14),
    "all_full": (2, 2, 1, None, False, [32, 32, 32]),
    # a cursor inside the window, at its edge, and past it by one and more
    "window": (2, 2, 1, 12, False, [0, 5, 11, 12, 13, 30, 32]),
    # a window of one position reads nothing cached: never visited
    "window_of_one": (2, 2, 1, 1, False, [0, 8, 9, 32]),
    "grouped_kv": (4, 1, 1, None, False, [0, 7, 16, 31]),
    "grouped_kv_window": (4, 1, 1, 10, False, [3, 10, 25, 0]),
    "verify_q5": (2, 2, 5, None, False, [0, 1, 9, 27]),
    "int8": (2, 2, 1, None, True, [0, 3, 8, 30]),
}
PARENT_OUTPUTS = os.path.join(os.path.dirname(__file__), "data",
                              "paged_parent_outputs.npz")


def walk_inputs(name):
    """Seeded float32 inputs of one case, as ``_paged_decode_pallas``
    takes them: ``q (S, h, q_len, d)``, the stacked pools, a scattered
    table, the cursors, the int8 scales or None twice."""
    h, hkv, q_len, _, int8, lengths = WALK_CASES[name]
    S = len(lengths)
    rng = np.random.RandomState(sorted(WALK_CASES).index(name))
    nb = S * WALK_NBS + 1
    tables = rng.permutation(np.arange(1, nb)).reshape(S, WALK_NBS)
    pool = lambda: rng.randn(2, nb, WALK_BS, hkv * WALK_D).astype(
        np.float32)
    q = rng.randn(S, h, q_len, WALK_D).astype(np.float32)
    kp, vp, ksc, vsc = pool(), pool(), None, None
    if int8:
        def quantize(x):
            x = x.reshape(2, nb, WALK_BS, hkv, WALK_D)
            xq, scale = _quantize_ref(x)       # scale (L, nb, bs, h)
            return (xq.reshape(2, nb, WALK_BS, hkv * WALK_D),
                    scale.transpose(0, 1, 3, 2))
        (kp, ksc), (vp, vsc) = quantize(kp), quantize(vp)
    return tuple(None if x is None else jnp.asarray(x) for x in (
        q, kp, vp, tables.astype(np.int32),
        np.asarray(lengths, np.int32), ksc, vsc))


def walk_items(lengths, window):
    """``paged_work_list`` as a loop: the blocks the parent's grid
    computed on (``j * block_size < length``, from the window's first)."""
    return [(s, j) for s, n in enumerate(lengths)
            for j in range(0 if window is None else
                           max(n - window + 1, 0) // WALK_BS, WALK_NBS)
            if j * WALK_BS < n]


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_the_kernel_walks_the_live_blocks_only(case):
    """The work list is the loop's items; on them the kernel's ``out`` and
    ``lse`` are the PARENT's (the ``max_seqs x blocks a slot`` grid of
    before PR 36; ``tests/data/paged_parent_outputs.npz`` holds what its
    ``_paged_decode_pallas`` returned for ``walk_inputs(case)``, layer 1,
    scale ``d ** -0.5``: the arithmetic is unchanged, so the two are
    bit-equal on one machine, and a float32 rounding apart where another
    CPU's matrix product sums in another order); a slot with no item is
    never visited and reads 0 / -inf, which merges to ``v_new``; and the
    whole call agrees with the gathered XLA reference."""
    h, hkv, q_len, window, int8, lengths = WALK_CASES[case]
    q, kp, vp, tables, cursors, ksc, vsc = walk_inputs(case)
    S = len(lengths)

    work = fa.paged_work_list(cursors, WALK_BS, WALK_NBS, window)
    items = walk_items(lengths, window)
    n = int(work.n_live)
    assert n == len(items) == int(work.count.sum())
    assert list(zip(work.slot[:n].tolist(), work.block[:n].tolist())) \
        == items
    assert work.slot.shape == work.block.shape and not work.slot[n:].any()
    visited = np.asarray(work.count) > 0
    assert visited.tolist() == [any(s == i for s, _ in items)
                                for i in range(S)]

    out, lse = fa._paged_decode_pallas(
        q, kp, vp, jnp.int32(WALK_LAYER), tables, cursors, ksc, vsc, work,
        scale=WALK_D ** -0.5, mean_context=None, window=window)
    with np.load(PARENT_OUTPUTS) as parent:
        np.testing.assert_allclose(np.asarray(out)[visited],
                                   parent[case + ".out"][visited],
                                   rtol=2e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(lse)[visited],
                                   parent[case + ".lse"][visited],
                                   rtol=2e-6, atol=1e-6)
    assert not np.asarray(out)[~visited].any()
    assert np.all(np.asarray(lse)[~visited] == -np.inf)

    rng = np.random.RandomState(99)
    new = lambda: jnp.asarray(rng.randn(S, hkv, q_len, WALK_D), jnp.float32)
    k_new, v_new = new(), new()
    if q_len == 1:
        q, k_new, v_new = q[:, :, 0], k_new[:, :, 0], v_new[:, :, 0]
    call = lambda use_pallas: paged_decode_attention(
        q, kp, vp, WALK_LAYER, tables, cursors, k_new=k_new, v_new=v_new,
        k_scale=ksc, v_scale=vsc, use_pallas=use_pallas, window=window)
    got = np.asarray(call(True))
    np.testing.assert_allclose(got, np.asarray(call(False)), rtol=2e-5,
                               atol=2e-6)
    # nothing cached to read: the token attends to itself alone (a verify
    # row also reads the drafts before it, so row 0 only)
    alone = np.repeat(np.asarray(v_new), h // hkv, axis=1)[~visited]
    if q_len == 1:
        np.testing.assert_allclose(got[~visited], alone, rtol=1e-6)
    else:
        np.testing.assert_allclose(got[~visited][:, :, 0], alone[:, :, 0],
                                   rtol=1e-6)
    assert np.all(np.isfinite(got))


def _append_all(pool, k_new, v_new, block_ids, offsets, method="append"):
    """Every layer's write, one layer at a time (how the layer scan of
    the decode program drives the pool)."""
    for layer in range(pool.num_layers):
        pool = getattr(pool, method)(layer, k_new[layer], v_new[layer],
                                     block_ids, offsets)
    return pool


def _heads(pool_array, num_heads):
    """A token-major pool array ``(L, NB, bs, H*D)`` viewed
    ``(L, NB, bs, H, D)``."""
    a = np.asarray(pool_array)
    return a.reshape(a.shape[:3] + (num_heads, -1))


class TestPagedKVCache:
    def test_append_and_null_masking(self):
        pool = PagedKVCache.create(2, 6, 3, 4, 5, dtype=jnp.float32)
        assert pool.k.shape == (2, 6, 4, 15)
        assert (pool.num_heads, pool.head_dim, pool.block_size) == (3, 5, 4)
        kn = jnp.arange(2 * 2 * 3 * 5, dtype=jnp.float32).reshape(2, 2, 3, 5)
        pool = _append_all(pool, kn, kn + 100, jnp.asarray([2, 3]),
                           jnp.asarray([1, 0]))
        np.testing.assert_allclose(_heads(pool.k, 3)[:, 2, 1],
                                   np.asarray(kn)[:, 0])
        np.testing.assert_allclose(_heads(pool.v, 3)[:, 3, 0],
                                   np.asarray(kn)[:, 1] + 100)
        # a null-targeted append (masked slot) lands in block 0 only
        pool2 = _append_all(pool, kn * 0 - 7, kn * 0 - 7,
                            jnp.asarray([0, 0]), jnp.asarray([0, 0]))
        np.testing.assert_allclose(_heads(pool2.k, 3)[:, 2, 1],
                                   np.asarray(kn)[:, 0])
        np.testing.assert_allclose(np.asarray(pool2.k)[:, 1:],
                                   np.asarray(pool.k)[:, 1:])

    def test_append_writes_one_layer(self):
        pool = PagedKVCache.create(3, 4, 2, 4, 3, dtype=jnp.float32)
        kn = jnp.ones((1, 2, 3))
        pool = pool.append(jnp.int32(1), kn, 2 * kn, jnp.asarray([2]),
                           jnp.asarray([3]))
        k = _heads(pool.k, 2)
        assert np.all(k[1, 2, 3] == 1) and np.all(
            _heads(pool.v, 2)[1, 2, 3] == 2)
        assert not np.any(k[[0, 2]])          # layers 0 and 2 untouched
        assert np.count_nonzero(k[1]) == k[1, 2, 3].size

    def test_write_prompt_blocks_layout(self):
        L, H, P, D, bs = 2, 3, 8, 5, 4
        pool = PagedKVCache.create(L, 6, H, bs, D, dtype=jnp.float32)
        kp = jnp.arange(L * H * P * D, dtype=jnp.float32).reshape(L, H, P, D)
        pool = pool.write_prompt_blocks(kp, kp + 5, jnp.asarray([4, 5]))
        # block 4 holds positions 0..3, block 5 positions 4..7, each
        # position's heads side by side
        tok_major = np.asarray(kp).transpose(0, 2, 1, 3)   # (L, P, H, D)
        np.testing.assert_allclose(_heads(pool.k, H)[:, 4],
                                   tok_major[:, 0:4])
        np.testing.assert_allclose(_heads(pool.v, H)[:, 5],
                                   tok_major[:, 4:8] + 5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.int8])
    def test_decode_reads_back_the_rows_prefill_wrote(self, dtype):
        """The prefill's transpose to token-major against the kernel's
        reading of it: with a one-hot softmax (a huge matching key) the
        attention output IS one written V row, so every position of
        every head must come back exactly where the prompt put it."""
        L, H, P, D, bs = 2, 3, 8, 8, 4
        rng = np.random.RandomState(0)
        pool = PagedKVCache.create(L, 6, H, bs, D, dtype=dtype)
        # keys: position p of head h is the unit vector (p + h) % D, so
        # no two positions of a head share a direction; values: random
        k = np.zeros((L, H, P, D), np.float32)
        for h in range(H):
            for pos in range(P):
                k[:, h, pos, (pos + h) % D] = 1.0
        v = rng.randint(-8, 8, (L, H, P, D)).astype(np.float32)
        pool = pool.write_prompt_blocks(jnp.asarray(k), jnp.asarray(v),
                                        jnp.asarray([5, 2]))
        tables = jnp.asarray([[5, 2]], jnp.int32)
        for layer in range(L):
            for pos in range(P):
                # query head h = its own key at `pos`, scaled up: the
                # softmax over the cached positions is one-hot at `pos`
                q = jnp.asarray(k[layer, :, pos] * 1e4)[None]
                out = paged_decode_attention(
                    q, pool.k, pool.v, layer, tables,
                    jnp.asarray([P], jnp.int32), k_scale=pool.k_scale,
                    v_scale=pool.v_scale)
                np.testing.assert_allclose(
                    np.asarray(out)[0], v[layer, :, pos],
                    atol=0.1 if dtype == jnp.int8 else 1e-6)

    def test_cow_copy_and_null_noop(self):
        pool = PagedKVCache.create(1, 4, 2, 4, 3, dtype=jnp.float32)
        kn = jnp.ones((1, 1, 2, 3))
        pool = _append_all(pool, kn, 2 * kn, jnp.asarray([2]),
                           jnp.asarray([0]))
        pool = pool.cow_copy(jnp.asarray([2]), jnp.asarray([3]))
        np.testing.assert_allclose(np.asarray(pool.k)[:, 3],
                                   np.asarray(pool.k)[:, 2])
        assert np.any(np.asarray(pool.k)[:, 3])
        # the all-null pair is the no-op every COW-free step runs
        pool2 = pool.cow_copy(jnp.asarray([0]), jnp.asarray([0]))
        np.testing.assert_allclose(np.asarray(pool2.k), np.asarray(pool.k))

    def test_cow_copy_pairs_in_slot_order(self):
        """A later pair may take an earlier pair's released source as
        its target (``BlockAllocator.prepare_verify``): the earlier copy
        must have read it by then."""
        pool = PagedKVCache.create(2, 5, 1, 2, 2, dtype=jnp.float32)
        k = np.zeros(pool.k.shape, np.float32)
        for blk in range(5):
            k[:, blk] = blk
        pool = PagedKVCache(jnp.asarray(k), jnp.asarray(-k), 1)
        # slot 0: 1 -> 3; slot 1: 2 -> 1 (block 1 reused); slot 2 null
        pool = pool.cow_copy(jnp.asarray([1, 2, 0]), jnp.asarray([3, 1, 0]))
        got = np.asarray(pool.k)[0, :, 0, 0]
        np.testing.assert_array_equal(got, [0, 2, 2, 1, 4])
        np.testing.assert_array_equal(np.asarray(pool.v)[1, :, 1, 1],
                                      [0, -2, -2, -1, -4])

    def test_int8_pool_roundtrip_and_pytree(self):
        pool = PagedKVCache.create(1, 3, 2, 4, 8, dtype=jnp.int8)
        assert pool.quantized
        x = jnp.asarray(np.random.RandomState(0).randn(1, 1, 2, 8),
                        jnp.float32)
        pool = _append_all(pool, x, x, jnp.asarray([1]), jnp.asarray([2]))
        # block 1, position 2: each head's 8 lanes against its own scale
        deq = (_heads(pool.k, 2)[0, 1, 2].astype(np.float32)
               * np.asarray(pool.k_scale)[0, 1, :, 2, None])
        np.testing.assert_allclose(deq, np.asarray(x[0, 0]),
                                   atol=float(jnp.max(jnp.abs(x)) / 127.0)
                                   + 1e-6)
        leaves, treedef = jax.tree_util.tree_flatten(pool)
        assert len(leaves) == 4
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert back.quantized and back.num_heads == 2 and back.head_dim == 8
        fp = PagedKVCache.create(1, 3, 2, 4, 8)
        assert len(jax.tree_util.tree_leaves(fp)) == 2
        assert jax.tree_util.tree_map(lambda x: x, fp).num_heads == 2

    def test_block_bytes(self):
        assert paged_block_bytes(12, 12, 16, 64, jnp.bfloat16) == \
            2 * 12 * 12 * 64 * 2 * 16
        pool = PagedKVCache.create(12, 4, 12, 16, 64, dtype=jnp.bfloat16)
        assert pool.nbytes() == 4 * paged_block_bytes(12, 12, 16, 64,
                                                      jnp.bfloat16)
        q8 = PagedKVCache.create(12, 4, 12, 16, 64, dtype=jnp.int8)
        assert q8.nbytes() == 4 * paged_block_bytes(12, 12, 16, 64,
                                                    jnp.int8)


# ---------------------------------------------------------------------------
# the host-side block allocator
# ---------------------------------------------------------------------------

class TestBlockAllocator:
    def _alloc(self, num_blocks=10, block_size=4, blocks_per_slot=4,
               max_seqs=3):
        return BlockAllocator(num_blocks, block_size, blocks_per_slot,
                              max_seqs)

    def test_refcount_lifecycle_admit_share_cow_retire_free(self):
        a = self._alloc()
        prompt = list(range(8))                  # exactly 2 blocks
        plan = a.admit(0, prompt, prefill_blocks=2)
        assert plan.prefill and len(plan.block_row) == 2
        a.register_prefix(0, prompt)
        b0, b1 = int(a.tables[0, 0]), int(a.tables[0, 1])
        assert a.refcount[b0] == 1 and a.refcount[b1] == 1
        # share: full-cover hit maps both blocks, refcount++
        plan2 = a.admit(1, prompt, prefill_blocks=2)
        assert not plan2.prefill and plan2.cow_pending
        assert plan2.shared_tokens == 7 and len(plan2.suffix) == 1
        assert a.refcount[b0] == 2 and a.refcount[b1] == 2
        # COW: the cursor (7) is inside the last shared block
        step = a.prepare_step([1])
        new = int(step.cow_dst[1])
        assert int(step.cow_src[1]) == b1 and new not in (0, b1)
        assert a.cow_copies == 1
        assert a.refcount[b1] == 1 and a.refcount[new] == 1
        assert int(a.tables[1, 1]) == new
        a.advance([1])
        # retire the sharer: its private COW block frees, the shared
        # b0 drops to slot 0's reference
        a.release(1)
        assert a.refcount[b0] == 1 and a.refcount[new] == 0
        # retire the owner: registered blocks PARK in the prefix cache
        # (refcount 0, still indexed) instead of freeing outright
        a.release(0)
        assert a.refcount[b0] == 0 and a.refcount[b1] == 0
        assert a.free_blocks == 9                # everything reusable
        # the parked prefix still hits
        plan3 = a.admit(2, prompt, prefill_blocks=2)
        assert not plan3.prefill and a.refcount[b0] == 1

    def test_pool_exhaustion_rejects_and_rolls_back(self):
        a = self._alloc(num_blocks=4, blocks_per_slot=3)
        a.admit(0, list(range(8)), prefill_blocks=3)     # takes 2 of 3
        free_before = a.free_blocks
        with pytest.raises(PoolExhausted):
            a.admit(1, list(range(100, 108)), prefill_blocks=3)
        assert a.free_blocks == free_before              # rolled back
        assert not a.tables[1].any()

    def test_prefix_hash_collision_falls_back_to_full_prefill(self,
                                                              monkeypatch):
        a = self._alloc()
        monkeypatch.setattr(BlockAllocator, "_digest",
                            staticmethod(lambda parent, chunk: b"COLLIDE"))
        a.admit(0, list(range(8)), prefill_blocks=2)
        a.register_prefix(0, list(range(8)))
        # every digest collides now — the stored-chunk verification must
        # read a DIFFERENT prompt as a miss, never serve slot 0's KV
        assert a.lookup(list(range(100, 108))) == []
        plan = a.admit(1, list(range(100, 108)), prefill_blocks=2)
        assert plan.prefill
        # the identical prompt still verifies and hits (only the FIRST
        # chunk: under a total collision the second chunk's digest is
        # already taken, so it was never registered — sharing degrades,
        # correctness doesn't)
        assert len(a.lookup(list(range(8)))) == 1

    def test_lru_eviction_unregisters_oldest(self):
        a = self._alloc(num_blocks=5, blocks_per_slot=3, max_seqs=4)
        a.admit(0, list(range(4)), prefill_blocks=1)
        a.register_prefix(0, list(range(4)))
        a.release(0)                              # 1 cached block
        a.admit(0, list(range(10, 14)), prefill_blocks=1)
        a.register_prefix(0, list(range(10, 14)))
        a.release(0)                              # 2 cached blocks
        assert len(a.lookup(list(range(4)))) == 1
        # demand 3 fresh blocks: free list has 2, so the OLDEST cached
        # block (prompt 0..3) is evicted and unregistered
        a.admit(1, list(range(20, 32)), prefill_blocks=3)
        assert a.lookup(list(range(4))) == []
        assert len(a.lookup(list(range(10, 14)))) == 1

    def test_append_targets_mask_inactive_and_saturated(self):
        a = self._alloc(num_blocks=10, block_size=2, blocks_per_slot=2,
                        max_seqs=3)
        a.admit(0, [1, 2], prefill_blocks=1)
        a.admit(1, [3, 4, 5], prefill_blocks=2)
        a.lengths[1] = 4                          # saturated
        bid, off = a.append_targets(np.asarray([True, True, True]))
        assert bid[0] == a.tables[0, 1] or bid[0] == a.tables[0, 0]
        assert bid[1] == 0                        # saturated -> null
        assert bid[2] == 0                        # inactive slot -> null


# ---------------------------------------------------------------------------
# PagedServingEngine contracts
# ---------------------------------------------------------------------------

def _tiny_model(max_position_embeddings=64):
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_attention_heads=4,
                    max_position_embeddings=max_position_embeddings,
                    compute_dtype=jnp.float32)
    model = GPTModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _paged_engine(model, params, **kw):
    kw.setdefault("max_seqs", 2)
    kw.setdefault("max_len", 24)
    kw.setdefault("prefill_len", 8)
    kw.setdefault("num_blocks", 16)
    kw.setdefault("block_size", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    return PagedServingEngine(model, params, **kw)


class TestPagedEngine:
    @pytest.mark.parametrize("cache_dtype,tol", [
        (jnp.float32, 2e-4), (jnp.bfloat16, 0.1), (jnp.int8, 0.25)])
    def test_prefill_decode_parity_vs_one_shot(self, cache_dtype, tol):
        model, params = _tiny_model()
        eng = _paged_engine(model, params, cache_dtype=cache_dtype)
        rng = np.random.RandomState(0)
        prompt = [int(t) for t in rng.randint(1, 97, 7)]
        tok = eng.prefill(prompt, 0)
        toks = np.zeros(2, np.int32)
        temps = np.zeros(2, np.float32)
        active = np.asarray([True, False])
        seq = list(prompt) + [tok]
        for _ in range(4):
            toks[0] = seq[-1]
            out = eng.decode(toks, temps, active=active)
            one_shot = model(params, jnp.asarray(seq, jnp.int32)[None])
            # greedy parity: the engine's sampled token must equal the
            # one-shot argmax whenever the cache noise doesn't flip a
            # near-tie — assert on logit closeness via the argmax
            seq.append(int(out[0]))
        ref = model(params, jnp.asarray(seq[:-1], jnp.int32)[None])
        assert int(jnp.argmax(ref[0, -1])) == seq[-1]

    def test_prefix_shared_stream_identical_to_unshared(self):
        model, params = _tiny_model()
        eng = _paged_engine(model, params)
        prompt = [5, 9, 1, 33, 7, 21, 2, 40]
        t0 = eng.prefill(prompt, 0)
        assert eng.last_admit.prefill
        cold = [t0]
        toks = np.zeros(2, np.int32)
        temps = np.zeros(2, np.float32)
        for _ in range(5):
            toks[0] = cold[-1]
            out = eng.decode(toks, temps,
                             active=np.asarray([True, False]))
            cold.append(int(out[0]))
        # the same prompt admits into slot 1 as a prefix HIT and must
        # produce the identical greedy stream
        t1 = eng.prefill(prompt, 1)
        plan = eng.last_admit
        assert not plan.prefill and plan.shared_tokens == len(prompt) - 1
        assert eng.allocator.prefix_hits == 1
        shared = [t1]
        for _ in range(5):
            toks[1] = shared[-1]
            out = eng.decode(toks, temps,
                             active=np.asarray([False, True]))
            shared.append(int(out[1]))
        assert shared == cold

    def test_zero_recompile_across_admit_cow_retire(self):
        from apex_tpu.analysis.program import recompile_guard
        model, params = _tiny_model()
        eng = _paged_engine(model, params)
        prompt = [3, 1, 4, 1, 5, 9, 2, 6]
        reg = MetricsRegistry()
        sched = SlotScheduler(eng, registry=reg)
        with recompile_guard("paged admit/COW/retire") as guard:
            # warmup: first dispatch of the three programs is legit
            sched.run([Request(prompt=prompt, max_new_tokens=2)])
            guard.rebase()
            # steady state: cold admissions, prefix hits (COW), decode
            # grid steps, retirements — all on the same three programs
            reqs = [Request(prompt=prompt, max_new_tokens=3),
                    Request(prompt=prompt, max_new_tokens=3),
                    Request(prompt=[7, 7, 7], max_new_tokens=2)]
            sched.run(reqs)
        assert eng.allocator.prefix_hits >= 1
        assert eng.allocator.cow_copies >= 1
        snap = dict(reg.snapshot())
        assert snap.get("serve/prefix_hits", 0) >= 1
        assert snap.get("serve/blocks_cow_copied", 0) >= 1
        assert snap.get("serve/pool_blocks_free", 0) > 0
        assert snap.get("serve/ttft_prefix_ms_count", 0) >= 1

    def test_donation_lint_passes_and_swap_params(self):
        # construction runs lint_serving_engine (donation + aliasing on
        # all three programs); swap re-runs it
        model, params = _tiny_model()
        eng = _paged_engine(model, params)
        eng.swap_params(jax.tree_util.tree_map(lambda x: x * 1.01, params))
        assert eng.swaps == 1

    def test_pool_exhausted_submit_rejection_and_queueing(self):
        model, params = _tiny_model()
        # pool of 3 allocatable blocks; the prefill window admits up to
        # 16 tokens (4 blocks) so the pool is the binding constraint
        eng = _paged_engine(model, params, num_blocks=4, max_len=16,
                            prefill_len=16)
        sched = SlotScheduler(eng, registry=MetricsRegistry())
        # a prompt that could NEVER fit the pool: typed rejection
        r = sched.submit(Request(prompt=list(range(1, 17)),
                                 max_new_tokens=1))
        assert isinstance(r, Rejection) and r.reason == "pool_exhausted"
        # transient pressure queues instead: two 8-token prompts want
        # 2 blocks each + a decode block, pool has 3
        a = sched.submit(Request(prompt=[1, 2, 3, 4, 5, 6, 7, 8],
                                 max_new_tokens=2))
        b = sched.submit(Request(prompt=[11, 12, 13, 14, 15, 16, 17, 18],
                                 max_new_tokens=2))
        assert not isinstance(a, Rejection) and not isinstance(b, Rejection)
        for _ in range(30):
            if not sched.pending:
                break
            sched.step()
        assert {c.request_id for c in sched.completed} == {a, b}
        assert all(len(c.tokens) >= 1 for c in sched.completed)

    def test_pool_exhaustion_mid_decode_retires_capacity(self):
        model, params = _tiny_model()
        # 2 allocatable blocks of 4: one 4-token prompt takes 1 block,
        # decode grows into the 2nd, then the pool is dry
        eng = _paged_engine(model, params, num_blocks=3, max_len=16,
                            prefill_len=4, max_seqs=1)
        sched = SlotScheduler(eng, registry=MetricsRegistry())
        rid = sched.submit(Request(prompt=[1, 2, 3, 4],
                                   max_new_tokens=12))
        for _ in range(20):
            if not sched.pending:
                break
            sched.step()
        (comp,) = sched.completed
        assert comp.request_id == rid
        # ran out of pool before max_new_tokens: loud capacity retire,
        # not silent corruption
        assert comp.finish_reason == "capacity"
        assert 1 <= len(comp.tokens) < 12

    def test_suggest_pool_blocks_capacity_math(self):
        model, params = _tiny_model()
        eng = _paged_engine(model, params)
        hbm = 16 * 2 ** 30
        blocks = eng.suggest_pool_blocks(hbm, mean_len=128)
        assert blocks > 0
        # monotonic in HBM, and the per-block unit is honest
        assert eng.suggest_pool_blocks(2 * hbm, mean_len=128) >= blocks
        assert eng.block_bytes() == paged_block_bytes(
            model.cfg.num_layers, model.cfg.num_attention_heads,
            eng.block_size, model.cfg.head_dim, jnp.float32)
        # mean-length math: more blocks -> more concurrent sequences
        assert eng.suggest_max_seqs_for_pool(129, mean_len=128.0) == 4
        assert eng.suggest_max_seqs_for_pool(129, mean_len=256.0) == 2


# ---------------------------------------------------------------------------
# the pyprof cost model prices paged decode O(actual context)
# ---------------------------------------------------------------------------

class TestPagedCostModel:
    def test_paged_decode_prices_mean_context_not_max_len(self):
        from apex_tpu.pyprof.model import model_program
        # contexts long enough that the KV stream outweighs the fixed
        # per-call traffic (the block-diagonal query and output tiles)
        MAX_LEN, MEAN = 2048, 256
        model, params = _tiny_model(max_position_embeddings=MAX_LEN)
        # told no mean context, the estimate prices the whole table span
        whole = _paged_engine(model, params, max_len=MAX_LEN, num_blocks=40)
        paged = _paged_engine(model, params, max_len=MAX_LEN,
                              num_blocks=40, mean_context=MEAN)
        da = model_program(whole.decode_traced).regions["decode_attention"]
        pa = model_program(paged.decode_traced).regions["decode_attention"]
        ratio = pa.hbm_bytes / da.hbm_bytes
        # the modeled HBM is ~mean/max of the whole span's — the
        # O(max_len) gap, closed
        assert ratio <= (MEAN / MAX_LEN) * 1.5, ratio
        # and it scales WITH the context, not the pool span
        paged2 = _paged_engine(model, params, max_len=MAX_LEN,
                               num_blocks=40, mean_context=4 * MEAN)
        pa2 = model_program(paged2.decode_traced).regions[
            "decode_attention"]
        assert pa2.hbm_bytes > 2 * pa.hbm_bytes


# ---------------------------------------------------------------------------
# the default pool: every slot can reach max_len
# ---------------------------------------------------------------------------

class TestDefaultPool:
    def test_paged_serving_engine_is_the_one_engine(self):
        assert PagedServingEngine is ServingEngine

    @pytest.mark.parametrize("buckets,block", [
        (8, 8), (12, 4), ([16, 24], 8), (128, 128), ([256, 512], 128),
        (7, 1)])
    def test_block_size_defaults_to_the_buckets_common_factor_with_128(
            self, buckets, block):
        """``gcd(128, *buckets)``: the largest block that every prefill
        bucket is whole blocks of and that divides the kernel's 128-token
        tile. One rule for every caller: a bucket that shares no factor
        with 128 gets blocks of one token, legal and slow
        (docs/SERVING.md, "How to size the pool")."""
        model, params = _tiny_model(max_position_embeddings=512)
        widest = max(buckets) if isinstance(buckets, list) else buckets
        eng = ServingEngine(model, params, max_seqs=1, max_len=widest,
                            prefill_len=buckets)
        assert eng.block_size == block
        assert eng.num_blocks == -(-widest // block) + 1

    def test_every_slot_reaches_max_len_and_not_a_token_more(self):
        """The default pool is the whole reservation: ``max_seqs``
        sequences of ``max_len`` tokens each never find the pool
        exhausted, and the token after that has no block to land in."""
        model, params = _tiny_model()
        S, MAX_LEN = 3, 16
        eng = ServingEngine(model, params, max_seqs=S, max_len=MAX_LEN,
                            prefill_len=8, cache_dtype=jnp.float32)
        assert eng.num_blocks == S * 2 + 1 and eng.block_size == 8
        for slot in range(S):
            eng.prefill([1 + slot, 2, 3, 4, 5], slot)   # distinct prompts
        toks, temps = np.zeros(S, np.int32), np.zeros(S, np.float32)
        for _ in range(MAX_LEN - 5):
            toks = eng.decode(toks, temps)
            assert eng.last_failed == []
        assert eng.allocator.lengths.tolist() == [MAX_LEN] * S
        assert eng.allocator.free_blocks == 0
        eng.decode(toks, temps)
        assert eng.last_failed == [0, 1, 2]
        assert eng.allocator.lengths.tolist() == [MAX_LEN] * S
        # under the scheduler the same thing is a loud "capacity"
        for slot in range(S):
            eng.release_slot(slot)
        out = SlotScheduler(eng, registry=MetricsRegistry()).run(
            [Request(prompt=[1 + i, 2, 3, 4, 5], max_new_tokens=50)
             for i in range(S)])
        for c in out.values():
            assert c.finish_reason == "capacity"
            # the last token sampled is the one whose KV has no room
            assert len(c.tokens) == MAX_LEN - 5

    @pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
    def test_bytes_per_slot_times_slots_is_the_pool_less_its_null_block(
            self, cache_dtype):
        model, params = _tiny_model()
        eng = ServingEngine(model, params, max_seqs=3, max_len=20,
                            prefill_len=8, cache_dtype=cache_dtype)
        # 20 tokens in blocks of 8: a slot reserves 3 blocks
        assert eng.bytes_per_slot() == 3 * eng.block_bytes()
        assert eng.bytes_per_slot() * eng.max_seqs == \
            eng.cache.nbytes() - eng.block_bytes()
        hbm = 1 << 30
        assert eng.suggest_max_seqs(hbm) == \
            eng.suggest_pool_blocks(hbm, mean_len=20) // 3

    def test_explicit_defaults_lower_to_the_same_programs(self):
        model, params = _tiny_model()
        kw = dict(max_seqs=2, max_len=24, prefill_len=[8, 16],
                  speculate_k=2)
        left_out = ServingEngine(model, params, **kw)
        spelled = ServingEngine(model, params, num_blocks=2 * 3 + 1,
                                block_size=8, **kw)
        for name in ("prefill_traced", "decode_traced", "verify_traced"):
            assert getattr(left_out, name).lower().as_text() == \
                getattr(spelled, name).lower().as_text(), name
        assert program_text(left_out.release_compiled) == \
            program_text(spelled.release_compiled)

"""Flash attention kernel parity tests (vs XLA reference attention).

Model: ``reference:apex/contrib/test/fmha/test_fmha.py`` (kernel vs Python
attention) and ``apex/contrib/test/multihead_attn/`` (fast vs default impl).
The Pallas kernels run in interpreter mode on the CPU test backend.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import flash_attention, mha_reference, supports_flash


def _qkv(b=2, h=2, sq=256, sk=256, d=64, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(b, h, sq, d), dtype) * 0.3
    k = jnp.asarray(rng.randn(b, h, sk, d), dtype) * 0.3
    v = jnp.asarray(rng.randn(b, h, sk, d), dtype) * 0.3
    return q, k, v


# Every static branch of the kernels' schedule (flash_tile_plan): the
# chooser's own tile, whose one diagonal sub-tile is walked in strips;
# explicit grid tiles, equal and unequal; sub-tiles smaller than the tile
# (3 x 3 of 128 in a tile of 384: the dynamic loops); sk > sq (the
# offset); sk < sq (rows wholly masked in their first tile).
LAYOUTS = {
    "auto": dict(sq=256, sk=256),
    "equal_blocks": dict(sq=256, sk=256, block_q=128, block_k=128),
    "wide_blocks": dict(sq=256, sk=256, block_q=128, block_k=256),
    "tall_blocks": dict(sq=256, sk=256, block_q=256, block_k=128),
    "sub_tiles": dict(sq=768, sk=768, block_q=384, block_k=384),
    "offset": dict(sq=128, sk=384, block_q=128, block_k=384),
    "masked_rows": dict(sq=384, sk=128),
}


def _layout(name, dtype, seed, b=1, h=2):
    """``(q, k, v, block keywords, tolerance scale)`` of one LAYOUTS case."""
    kw = dict(LAYOUTS[name])
    q, k, v = _qkv(b=b, h=h, sq=kw.pop("sq"), sk=kw.pop("sk"), seed=seed,
                   dtype=dtype)
    return q, k, v, kw, 1.0 if dtype == jnp.float32 else 1e3


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_matches_reference(causal, layout, dtype):
    q, k, v, blocks, loose = _layout(layout, dtype, seed=0, b=2)
    out = flash_attention(q, k, v, causal=causal, use_pallas=True, **blocks)
    ref = mha_reference(q, k, v, causal=causal)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-5 * loose, atol=2e-5 * loose)


def test_flash_with_bias_mask():
    q, k, v = _qkv(seed=1)
    rng = np.random.RandomState(2)
    mask = rng.rand(2, 1, 256, 256) > 0.8
    bias = jnp.where(jnp.asarray(mask), -10000.0, 0.0).astype(jnp.float32)
    out = flash_attention(q, k, v, bias=bias, use_pallas=True)
    ref = mha_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_matches_reference(causal, layout, dtype):
    """dq, dk and dv of the one backward kernel, whose dq gathers over the
    whole head while dk and dv gather a kv tile at a time."""
    q, k, v, blocks, loose = _layout(layout, dtype, seed=3)
    dy = jnp.asarray(np.random.RandomState(4).randn(*q.shape), jnp.float32)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       use_pallas=True, **blocks) * dy)

    def f_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) * dy)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        assert a.dtype == dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-4 * loose / 4, atol=2e-4 * loose / 4)


def test_flash_bwd_with_bias():
    q, k, v = _qkv(b=1, h=1, sq=128, sk=256, seed=5)
    mask = np.random.RandomState(6).rand(1, 1, 128, 256) > 0.9
    bias = jnp.where(jnp.asarray(mask), -10000.0, 0.0).astype(jnp.float32)
    dy = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32)

    def f(q, k, v, use_pallas):
        return jnp.sum(flash_attention(q, k, v, bias=bias,
                                       use_pallas=use_pallas) * dy)

    g_flash = jax.grad(lambda a, b, c: f(a, b, c, True),
                       argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda a, b, c: f(a, b, c, False),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_cross_attention_causal_offset():
    # sq != sk causal: the mask is offset so the last query row sees all keys
    q, k, v = _qkv(b=1, h=1, sq=128, sk=256, seed=8)
    out = flash_attention(q, k, v, causal=True, use_pallas=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bf16_path():
    q, k, v = _qkv(seed=9, dtype=jnp.bfloat16, sq=128, sk=128)
    out = flash_attention(q, k, v, causal=True, use_pallas=True)
    ref = mha_reference(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=2e-2, atol=2e-2)


def test_bwd_fully_masked_rows_block_misaligned():
    """ADVICE r1 (medium): causal with sk<sq leaves rows 0..(sq-sk-1) fully
    masked; when block_q straddles the masked-row boundary (block_q=24 does
    not divide 128) the backward used to produce exp(-1e30 - -1e30) = 1
    garbage p on those rows, contaminating dk/dv (~7.5 abs divergence)."""
    q, k, v = _qkv(b=1, h=1, sq=240, sk=128, seed=11)
    assert supports_flash(240, 128, 64, 24, 128)
    n_masked = 240 - 128  # rows with no visible keys
    dy = np.random.RandomState(12).randn(1, 1, 240, 64)
    dy[:, :, :n_masked] = 0.0  # fully-masked rows are undefined: exclude
    dy = jnp.asarray(dy, jnp.float32)

    def f(q, k, v, use_pallas):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       block_q=24, block_k=128,
                                       use_pallas=use_pallas) * dy)

    g_flash = jax.grad(lambda a, b, c: f(a, b, c, True),
                       argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda a, b, c: f(a, b, c, False),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    # and the flash fwd output on fully-masked rows is exactly zero
    out = flash_attention(q, k, v, causal=True, block_q=24, block_k=128,
                          use_pallas=True)
    assert np.all(np.asarray(out)[:, :, :n_masked] == 0.0)


@pytest.mark.parametrize("seq,blocks", [
    (128, {}),                                  # one tile
    (256, dict(block_q=128, block_k=128)),      # 2 x 2 tiles of one sub-tile
])
@pytest.mark.parametrize("bias_shape", [
    (1, 2, 0, 0),   # shared over batch (rel-pos table)
    (2, 2, 0, 0),   # full (no reduction)
    (1, 1, 0, 0),   # shared over batch and heads
    (2, 1, 0, 0),   # shared over heads
    (1, 2, 1, 0),   # broadcast over sq too (ALiBi-style row)
])
def test_dbias_learned_bias(bias_shape, seq, blocks):
    """bias_requires_grad=True returns the real dbias (score cotangent summed
    over broadcast dims), matching the XLA fallback's bias grad; and the
    q, k, v gradients beside it, with the bias in the scores."""
    q, k, v = _qkv(b=2, h=2, sq=seq, sk=seq, seed=13)
    bias = jnp.asarray(np.random.RandomState(14).randn(
        *(n or seq for n in bias_shape)) * 0.1, jnp.float32)
    dy = jnp.asarray(np.random.RandomState(15).randn(*q.shape), jnp.float32)

    def f(bias, use_pallas, q=q, k=k, v=v):
        return jnp.sum(flash_attention(
            q, k, v, bias=bias, causal=True, use_pallas=use_pallas,
            bias_requires_grad=True, **blocks) * dy)

    for a, b in zip(*(jax.grad(lambda q, k, v: f(bias, flag, q, k, v),
                               argnums=(0, 1, 2))(q, k, v)
                      for flag in (True, False))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)

    db_flash = jax.grad(lambda b: f(b, True))(bias)
    db_ref = jax.grad(lambda b: f(b, False))(bias)
    np.testing.assert_allclose(np.asarray(db_flash), np.asarray(db_ref),
                               rtol=2e-4, atol=2e-4)


def test_dbias_zero_by_default_both_paths():
    """Without bias_requires_grad the bias grad is zero on the Pallas path
    AND the XLA fallback (semantics must not flip with tile alignment)."""
    q, k, v = _qkv(b=2, h=2, sq=128, sk=128, seed=13)
    bias = jnp.asarray(np.random.RandomState(14).randn(1, 2, 128, 128) * 0.1,
                       jnp.float32)
    dy = jnp.asarray(np.random.RandomState(15).randn(*q.shape), jnp.float32)
    for use_pallas in (True, False):
        db = jax.grad(lambda b: jnp.sum(flash_attention(
            q, k, v, bias=b, use_pallas=use_pallas) * dy))(bias)
        assert np.all(np.asarray(db) == 0.0)


def test_padding_mask_broadcast_shapes():
    """Padding-style biases keep their broadcast shape ((b,1,1,sk) costs
    O(b·sk) HBM, ADVICE r1) and still match the reference."""
    q, k, v = _qkv(b=2, h=2, sq=128, sk=256, seed=16)
    rng = np.random.RandomState(17)
    for shape in [(2, 1, 1, 256), (1, 1, 128, 256), (1, 2, 128, 256),
                  (2, 2, 1, 256)]:
        b_ = jnp.where(jnp.asarray(rng.rand(*shape) > 0.2),
                       0.0, -10000.0).astype(jnp.float32)
        out = flash_attention(q, k, v, bias=b_, use_pallas=True)
        ref = mha_reference(q, k, v, bias=b_)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layout", ["auto", "equal_blocks", "wide_blocks",
                                    "sub_tiles", "offset"])
def test_dropout_matches_reference_mask(layout):
    """In-kernel dropout (philox analog) agrees with the XLA reference using
    the same counter-derived mask — forward AND all gradients: the mask is
    a function of the global (row, column), so whatever tile, sub-tile or
    strip a kernel walks regenerates the bits of ``dropout_keep_mask``."""
    q, k, v, blocks, _ = _layout(layout, jnp.float32, seed=20, b=2)
    dy = jnp.asarray(np.random.RandomState(21).randn(*q.shape), jnp.float32)
    seed = jnp.asarray(12345, jnp.int32)

    def f(q, k, v, use_pallas):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, dropout_rate=0.3, dropout_seed=seed,
            use_pallas=use_pallas, **blocks) * dy)

    out_fl = flash_attention(q, k, v, causal=True, dropout_rate=0.3,
                             dropout_seed=seed, use_pallas=True, **blocks)
    out_ref = flash_attention(q, k, v, causal=True, dropout_rate=0.3,
                              dropout_seed=seed, use_pallas=False)
    np.testing.assert_allclose(np.asarray(out_fl), np.asarray(out_ref),
                               rtol=2e-5, atol=2e-5)

    g_fl = jax.grad(lambda a, b, c: f(a, b, c, True), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda a, b, c: f(a, b, c, False), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-4)


def test_dropout_deterministic_and_seed_dependent():
    q, k, v = _qkv(b=1, h=2, sq=128, sk=128, seed=22)
    out1 = flash_attention(q, k, v, dropout_rate=0.5, dropout_seed=7,
                           use_pallas=True)
    out2 = flash_attention(q, k, v, dropout_rate=0.5, dropout_seed=7,
                           use_pallas=True)
    out3 = flash_attention(q, k, v, dropout_rate=0.5, dropout_seed=8,
                           use_pallas=True)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    assert not np.array_equal(np.asarray(out1), np.asarray(out3))
    # rate ~ 0.5: dropped entries show up as a large deviation from rate 0
    base = flash_attention(q, k, v, use_pallas=True)
    assert not np.allclose(np.asarray(out1), np.asarray(base))


def test_dropout_mask_statistics():
    from apex_tpu.ops.flash_attention import dropout_keep_mask
    m = np.asarray(dropout_keep_mask(3, 2, 2, 256, 256, 0.3))
    assert abs(m.mean() - 0.7) < 0.01
    # rows/cols not degenerate: no all-dropped row at this size
    assert m.any(axis=-1).all()


def test_dropout_requires_seed():
    q, k, v = _qkv(b=1, h=1, sq=128, sk=128)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, dropout_rate=0.1)


def test_bias_bad_shape_raises():
    q, k, v = _qkv(b=2, h=2, sq=128, sk=128, seed=18)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, bias=jnp.zeros((3, 1, 1, 128)),
                        use_pallas=True)


def test_unaligned_falls_back():
    q, k, v = _qkv(sq=100, sk=100, seed=10)
    assert not supports_flash(100, 100, 64, 128, 128)
    out = flash_attention(q, k, v)  # auto-fallback, must not raise
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dbias_learned_bias_with_dropout(dtype):
    """ADVICE r2: bias_requires_grad=True together with dropout_rate>0 —
    the dropout branch of the dbias kernel (ds rebuilt from the dropped
    probabilities) must match the XLA fallback, in fp32 and with bf16
    q/k/v."""
    q, k, v = _qkv(b=2, h=2, sq=128, sk=128, seed=23)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    bias = jnp.asarray(np.random.RandomState(24).randn(1, 2, 128, 128) * 0.1,
                       jnp.float32)
    dy = jnp.asarray(np.random.RandomState(25).randn(*q.shape), jnp.float32)

    def f(bias, use_pallas):
        return jnp.sum(flash_attention(
            q, k, v, bias=bias, causal=True, use_pallas=use_pallas,
            bias_requires_grad=True, dropout_rate=0.3,
            dropout_seed=987654321) * dy)

    db_flash = jax.grad(lambda b: f(b, True))(bias)
    db_ref = jax.grad(lambda b: f(b, False))(bias)
    tol = 2e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(db_flash), np.asarray(db_ref),
                               rtol=tol, atol=tol)


def test_seed_uses_full_32_bits():
    """ADVICE r2: seeds differing only above bit 24 must give different
    masks (the old fp32 carrier truncated to 24 bits)."""
    from apex_tpu.ops.flash_attention import dropout_keep_mask
    m1 = np.asarray(dropout_keep_mask(1, 1, 1, 64, 128, 0.5))
    m2 = np.asarray(dropout_keep_mask(1 + (1 << 25), 1, 1, 64, 128, 0.5))
    assert (m1 != m2).any()


# ---------------------------------------------------------------------------
# varlen / packed segments (reference:apex/contrib/csrc/fmha/fmha_api.cpp:420
# cu_seqlens role)
# ---------------------------------------------------------------------------

def _packed_ids(b, s, boundaries):
    ids = np.zeros((b, s), np.int32)
    for bi in range(b):
        seg = 0
        for pos in range(s):
            if pos in boundaries[bi]:
                seg += 1
            ids[bi, pos] = seg
    return jnp.asarray(ids)


@pytest.mark.parametrize("seq,blocks", [
    (128, {}),                                  # one tile
    (256, dict(block_q=128, block_k=128)),      # a segment across tiles
])
@pytest.mark.parametrize("causal", [False, True])
def test_segment_ids_match_reference(causal, seq, blocks):
    """Pallas segment masking == XLA fallback, forward and grads; a row
    whose segment starts in a later tile is wholly masked in the first."""
    q, k, v = _qkv(b=2, h=2, sq=seq, sk=seq, seed=31)
    ids = _packed_ids(2, seq, [{40, 90}, {seq - 64}])
    dy = jnp.asarray(np.random.RandomState(32).randn(*q.shape), jnp.float32)

    def f(q, k, v, use_pallas):
        return jnp.sum(flash_attention(
            q, k, v, causal=causal, use_pallas=use_pallas,
            segment_ids=ids, **blocks) * dy)

    out_p = flash_attention(q, k, v, causal=causal, use_pallas=True,
                            segment_ids=ids, **blocks)
    out_r = flash_attention(q, k, v, causal=causal, use_pallas=False,
                            segment_ids=ids)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=2e-4, atol=2e-4)
    g_p = jax.grad(lambda *a: f(*a, True), argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(lambda *a: f(*a, False), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_p, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_segments_are_isolated():
    """Packing semantics: segment A's outputs must not change when segment
    B's tokens change — the property cu_seqlens packing guarantees."""
    rng = np.random.RandomState(33)
    q, k, v = _qkv(b=1, h=2, sq=128, sk=128, seed=33)
    ids = _packed_ids(1, 128, [{64}])
    base = flash_attention(q, k, v, causal=True, use_pallas=True,
                           segment_ids=ids)
    # perturb the SECOND segment's keys/values
    k2 = k.at[:, :, 64:].set(jnp.asarray(rng.randn(1, 2, 64, 64),
                                         k.dtype))
    v2 = v.at[:, :, 64:].set(jnp.asarray(rng.randn(1, 2, 64, 64),
                                         v.dtype))
    pert = flash_attention(q, k2, v2, causal=True, use_pallas=True,
                           segment_ids=ids)
    np.testing.assert_allclose(np.asarray(base[:, :, :64]),
                               np.asarray(pert[:, :, :64]),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(base[:, :, 64:]),
                           np.asarray(pert[:, :, 64:]))


def test_segment_ids_with_dropout_and_bias():
    """Segments compose with in-kernel dropout and learned-bias grads."""
    q, k, v = _qkv(b=2, h=2, sq=128, sk=128, seed=34)
    ids = _packed_ids(2, 128, [{50}, {30, 100}])
    bias = jnp.asarray(np.random.RandomState(35).randn(1, 2, 128, 128) * 0.1,
                       jnp.float32)
    dy = jnp.asarray(np.random.RandomState(36).randn(*q.shape), jnp.float32)

    def f(bias, use_pallas):
        return jnp.sum(flash_attention(
            q, k, v, bias=bias, causal=True, use_pallas=use_pallas,
            bias_requires_grad=True, dropout_rate=0.2, dropout_seed=4242,
            segment_ids=ids) * dy)

    db_p = jax.grad(lambda b: f(b, True))(bias)
    db_r = jax.grad(lambda b: f(b, False))(bias)
    np.testing.assert_allclose(np.asarray(db_p), np.asarray(db_r),
                               rtol=2e-4, atol=2e-4)


def test_segment_ids_validation():
    q, k, v = _qkv(b=1, h=1, sq=128, sk=256, seed=37)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, segment_ids=jnp.zeros((1, 128), jnp.int32))
    out = flash_attention(
        q, k, v,
        segment_ids=(jnp.zeros((1, 128), jnp.int32),
                     jnp.zeros((1, 256), jnp.int32)))
    ref = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_tile_plan_at_the_cells_shapes():
    """The schedule as a pure function: what the kernels skip, leave
    unmasked and mask at the benchmark cells' shapes, with the tiles the
    chooser picks there."""
    from apex_tpu.ops.flash_attention import _auto_block, flash_tile_plan
    # gpt2-medium's train step: one grid tile a head, 2 x 2 sub-tiles of
    # 512; the two on the diagonal are walked in two strips (3/4 of each)
    assert _auto_block(1024, 1024, 64) == (1024, 1024, 512, 512)
    assert flash_tile_plan(1024, 1024, 1024, 1024, 512, 512) == dict(
        skipped=1, unmasked=1, masked=2, scores_needed=1024 * 1025 // 2,
        scores_computed=512 * 512 + 2 * (256 * 256 + 256 * 512))
    # gpt2-large's prefill bucket: the one diagonal sub-tile
    assert _auto_block(512, 512, 64) == (512, 512, 512, 512)
    assert flash_tile_plan(512, 512, 512, 512, 512, 512) == dict(
        skipped=0, unmasked=0, masked=1, scores_needed=512 * 513 // 2,
        scores_computed=256 * 256 + 256 * 512)
    # the pattern model's longest bucket under its window, 16 x 16
    # sub-tiles: every row band has its diagonal sub-tile masked, from the
    # 9th band on also the one the window's left edge crosses, and at most
    # 7 whole ones between; no strips under a window
    assert _auto_block(8192, 8192, 128) == (1024, 1024, 512, 512)
    plan = flash_tile_plan(8192, 8192, 1024, 1024, 512, 512, window=4096)
    assert plan["masked"] == 16 + 8
    assert plan["unmasked"] == sum(min(band, 7) for band in range(16))
    assert plan["skipped"] == 256 - plan["masked"] - plan["unmasked"]
    assert plan["scores_computed"] == 512 * 512 * (plan["masked"]
                                                   + plan["unmasked"])
    rows = np.arange(8192)
    assert plan["scores_needed"] == int(np.minimum(rows + 1, 4096).sum())
    # without causal nothing is skipped and nothing masked
    assert flash_tile_plan(256, 512, 256, 512, 128, 128, causal=False) == \
        dict(skipped=0, unmasked=8, masked=0, scores_needed=256 * 512,
             scores_computed=256 * 512)


# -- decode shapes (sq=1 vs a cached sk) — the serving kernel family's
#    entry points into this module; the cache-streaming kernel itself is
#    covered in tests/test_serving.py


def test_supports_flash_decode_shapes():
    """sq == 1 is a first-class shape: only the key-side tiling gates
    (the historical gate silently assumed sq == sk callers)."""
    assert supports_flash(1, 1024, 64, 1, 128)
    assert supports_flash(1, 256, 64, 1, 256)
    assert not supports_flash(1, 200, 64, 1, 128)   # sk misaligned
    assert not supports_flash(1, 256, 63, 1, 128)   # d misaligned
    assert not supports_flash(1, 256, 64, 8, 128)   # q tile must be 1
    # the training gate is unchanged
    assert supports_flash(256, 256, 64, 128, 128)
    assert not supports_flash(200, 256, 64, 128, 128)


def test_flash_sq1_pallas_matches_reference():
    """The generic flash entry point takes the Pallas path at sq=1
    (block_q=1, one padded sublane tile) and matches the reference —
    causal at sq=1 means 'attend to everything cached'."""
    q, k, v = _qkv(sq=1, sk=256, seed=11)
    out = flash_attention(q, k, v, causal=True, use_pallas=True)
    ref = mha_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # auto path selects Pallas for the aligned decode shape
    auto = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(auto), np.asarray(out),
                               rtol=2e-5, atol=2e-5)


def test_mha_reference_kv_length_oracle():
    """The kv_length oracle path: masks exactly like slicing the cache at
    the cursor, and zeroes empty rows."""
    q, k, v = _qkv(b=3, h=2, sq=1, sk=64, seed=12)
    lengths = jnp.asarray([0, 5, 64], jnp.int32)
    out = mha_reference(q, k, v, kv_length=lengths)
    assert np.all(np.asarray(out[0]) == 0.0)
    for i, L in ((1, 5), (2, 64)):
        ref = mha_reference(q[i:i + 1], k[i:i + 1, :, :L],
                            v[i:i + 1, :, :L])
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0]),
                                   rtol=2e-6, atol=2e-6)

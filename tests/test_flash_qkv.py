"""The packed flash kernels: attention over the fused QKV activation where
it lies (``flash_attention_qkv``), held to the XLA reference and to the
``(b, h, s, d)`` kernels fed the same q, k and v. The Pallas kernels run
in interpreter mode on the CPU test backend."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.analysis.jaxpr import iter_eqns

# the package re-exports the FUNCTION under the module's name
fa = importlib.import_module("apex_tpu.ops.flash_attention")


def _activation(b, h, s, d=64, seed=0, dtype=jnp.float32):
    """A QKV product ``(b, s, h * 3d)``, each head's ``[q | k | v]`` side
    by side."""
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(b, s, h * 3 * d) * 0.5, dtype)


def _split(qkv, h, **kw):
    """The heads split and transposed through the ``(b, h, s, d)``
    kernels: what every call did before the packed form."""
    q, k, v = fa.split_heads(qkv, h)
    return fa.merge_heads(fa.flash_attention(q, k, v, **kw))


def _reference(qkv, h, causal=True):
    q, k, v = fa.split_heads(qkv, h)
    return fa.merge_heads(fa.mha_reference(q, k, v, causal=causal))


def _reference_lse(qkv, h, causal=True):
    q, k, _ = fa.split_heads(qkv, h)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jax.nn.logsumexp(s, axis=-1)                      # (b, h, s)


# (b, heads, sequence, block_q, block_k): the chooser's own tile (one
# diagonal sub-tile walked in strips), explicit tiles smaller than the
# sequence, equal and unequal, so that both kernels gather across q and
# kv tiles; 3 x 3 sub-tiles of 128 in a tile of 384 (the dynamic loops);
# sixteen heads at 1,024
LAYOUTS = {
    "auto_128": (2, 2, 128, None, None),
    "tiles_128_of_256": (1, 4, 256, 128, 128),
    "wide_kv_tile": (1, 2, 256, 128, 256),
    "tall_q_tile": (1, 2, 256, 256, 128),
    "sub_tiles": (1, 2, 768, 384, 384),
    "sixteen_heads_1024": (1, 16, 1024, 256, 512),
}


def _case(name, dtype=jnp.float32, seed=0):
    b, h, s, block_q, block_k = LAYOUTS[name]
    return _activation(b, h, s, seed=seed, dtype=dtype), h, dict(
        block_q=block_q, block_k=block_k)


def _packed_calls(fn, x, width=None):
    """Operand shapes of the ``pallas_call``s in ``fn(x)``'s jaxpr that
    take the QKV activation whole (``width`` lanes, ``x``'s by default):
    the packed kernels; the split ones take ``(b * h, s, d)`` heads."""
    width = width or x.shape[-1]
    return [[v.aval.shape for v in eqn.invars]
            for eqn in iter_eqns(jax.make_jaxpr(fn)(x).jaxpr)
            if eqn.primitive.name == "pallas_call"
            and eqn.invars[0].aval.shape[-1] == width]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_packed_context_matches_reference_and_split_kernels(layout, causal):
    qkv, h, blocks = _case(layout)
    out = fa.flash_attention_qkv(qkv, h, causal=causal, use_pallas=True,
                                 **blocks)
    assert _packed_calls(
        lambda x: fa.flash_attention_qkv(x, h, causal=causal,
                                         use_pallas=True, **blocks), qkv)
    b, s = qkv.shape[:2]
    assert out.shape == (b, s, h * 64) and out.dtype == qkv.dtype
    np.testing.assert_allclose(out, _reference(qkv, h, causal),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(
        out, _split(qkv, h, causal=causal, use_pallas=True, **blocks),
        rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_packed_lse_rows_match_reference_and_split_kernels(layout):
    """The logsumexp leaves as rows ``(b * h / 2, 2, s)``: pair ``p``'s
    row 0 is head ``2p``, row 1 head ``2p + 1``."""
    qkv, h, blocks = _case(layout)
    b, s = qkv.shape[:2]
    tile = fa._packed_tile(s, 64, h, blocks["block_q"], blocks["block_k"])
    _, lse = fa._packed_fwd_pallas(qkv, h, scale=0.125, causal=True,
                                   tile=tile)
    assert lse.shape == (b * h // 2, 2, s)
    rows = lse.reshape(b, h, s)
    np.testing.assert_allclose(rows, _reference_lse(qkv, h), rtol=1e-5,
                               atol=1e-5)
    q, k, v = (x.reshape(b * h, s, 64) for x in fa.split_heads(qkv, h))
    _, split_lse = fa._fwd_pallas(q, k, v, None, None, None, h, scale=0.125,
                                  causal=True, tile=tile, dropout_rate=0.0)
    np.testing.assert_allclose(rows, split_lse.reshape(b, h, s), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_packed_qkv_gradient_matches_reference_and_split_kernels(layout):
    """dq, dk and dv land in the QKV layout, each head's where its q, k
    and v lie."""
    qkv, h, blocks = _case(layout)
    w = jnp.asarray(np.random.RandomState(1).randn(
        *qkv.shape[:2], h * 64), jnp.float32)

    def grad(fn):
        return jax.grad(lambda x: jnp.sum(fn(x) * w))(qkv)

    packed = grad(lambda x: fa.flash_attention_qkv(
        x, h, causal=True, use_pallas=True, **blocks))
    assert packed.shape == qkv.shape
    np.testing.assert_allclose(packed, grad(lambda x: _reference(x, h)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(
        packed, grad(lambda x: _split(x, h, causal=True, use_pallas=True,
                                      **blocks)), rtol=1e-5, atol=1e-5)


def test_packed_bfloat16_rounds_where_the_split_kernels_round():
    """bfloat16 operands: context and gradient within a bfloat16 ulp of
    the split kernels' at the gpt2 widths' head dim."""
    qkv, h, blocks = _case("tiles_128_of_256", dtype=jnp.bfloat16)
    w = jnp.asarray(np.random.RandomState(1).randn(
        *qkv.shape[:2], h * 64), jnp.bfloat16)

    def run(fn):
        out, vjp = jax.vjp(fn, qkv)
        return out, vjp(w)[0]

    out, g = run(lambda x: fa.flash_attention_qkv(
        x, h, causal=True, use_pallas=True, **blocks))
    out_s, g_s = run(lambda x: _split(x, h, causal=True, use_pallas=True,
                                      **blocks))
    assert out.dtype == g.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_s, np.float32), atol=1e-2)
    np.testing.assert_allclose(np.asarray(g, np.float32),
                               np.asarray(g_s, np.float32), atol=3e-2)


# calls the packed form does not take: today's path, to the bit
FALLBACKS = {
    "odd_heads": dict(h=3, d=64, kw={}),
    "head_dim_96": dict(h=2, d=96, kw={}),
    "dropout": dict(h=2, d=64, kw=dict(dropout_rate=0.1, dropout_seed=7)),
    "xla": dict(h=2, d=64, kw=dict(use_pallas=False)),
    "tile_of_64": dict(h=2, d=64, kw=dict(block_q=64)),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_calls_that_do_not_pack_take_the_split_heads_exactly(case):
    c = FALLBACKS[case]
    h, kw = c["h"], dict(causal=True, **c["kw"])
    qkv = _activation(1, h, 256, d=c["d"])

    def packed(x):
        return fa.flash_attention_qkv(x, h, **kw)

    assert _packed_calls(packed, qkv) == []
    np.testing.assert_array_equal(packed(qkv), _split(qkv, h, **kw))
    w = jnp.ones(qkv.shape[:2] + (h * c["d"],), jnp.float32)
    np.testing.assert_array_equal(
        jax.grad(lambda x: jnp.sum(packed(x) * w))(qkv),
        jax.grad(lambda x: jnp.sum(_split(x, h, **kw) * w))(qkv))


def test_gpt_attention_packs_only_where_no_kv_is_collected():
    """``GPTModel._attention`` takes the packed kernels for training and
    the plain forward; the paged prefill, which hands each layer's K and V
    to the cache, keeps the split heads (and its K/V are those heads)."""
    from apex_tpu.models import GPTConfig, GPTModel
    model = GPTModel(GPTConfig(vocab_size=256, hidden_size=256, num_layers=1,
                               num_attention_heads=4,
                               max_position_embeddings=256,
                               compute_dtype=jnp.float32))
    lp = jax.tree_util.tree_map(lambda x: x[0],
                                model.init(jax.random.PRNGKey(0))["layers"])
    x = _activation(1, 4, 256, d=64)[..., :256]

    def attend(x, collect_kv):
        return model._attention(lp, x, collect_kv=collect_kv)

    def qkv_calls(collect_kv):
        return _packed_calls(lambda x: attend(x, collect_kv), x, width=768)

    assert qkv_calls(False) and not qkv_calls(True)
    packed = attend(x, False)
    split, (k, v) = attend(x, True)
    np.testing.assert_allclose(packed, split, rtol=1e-5, atol=1e-5)
    qkv, _ = model.qkv(lp["qkv"], x)
    _, k_ref, v_ref = fa.split_heads(qkv, 4)
    np.testing.assert_array_equal(k, k_ref)
    np.testing.assert_array_equal(v, v_ref)


@pytest.mark.parametrize("policy", ["selective", "full"])
def test_packed_kernels_under_the_remat_policies(policy):
    """The packed pair under a remat policy: ``selective`` keeps both of
    its tagged residuals (``flash_ctx``, ``flash_lse``), so only the
    backward kernel is in the recomputed region; ``full`` reruns the
    forward there too. Either way the gradient is the unrematerialised
    one."""
    from apex_tpu.models import GPTConfig, GPTModel
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 256, (1, 128)))

    def grads(policy):
        model = GPTModel(GPTConfig(
            vocab_size=256, hidden_size=256, num_layers=2,
            num_attention_heads=4, max_position_embeddings=128,
            compute_dtype=jnp.float32, use_flash=True,
            remat_policy=policy))
        params = model.init(jax.random.PRNGKey(0))
        fn = jax.grad(lambda p: model.loss(p, tokens, tokens))
        return jax.make_jaxpr(fn)(params), fn(params)

    jaxpr, got = grads(policy)
    kernels = [e for e in iter_eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call"]
    assert kernels and all(e.invars[0].aval.shape[-1] == 768
                           for e in kernels)
    recomputed = sum(1 for e in iter_eqns(jaxpr.jaxpr)
                     if e.primitive.name in ("remat2", "checkpoint")
                     for inner in iter_eqns(e.params["jaxpr"])
                     if inner.primitive.name == "pallas_call")
    assert recomputed == (1 if policy == "selective" else 2)
    _, want = grads("none")
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)

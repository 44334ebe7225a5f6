"""Model zoo tests (``reference:tests/L0/run_transformer/run_gpt_minimal_test.py``,
``run_bert_minimal_test.py``; imagenet example smoke)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models import (
    BertConfig, BertModel, GPTConfig, GPTModel, ResNet50, ResNetConfig)
from apex_tpu.optimizers import FusedAdam
from apex_tpu.transformer import parallel_state


def _small_gpt(tp=1, **kw):
    return GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                     num_attention_heads=4, max_position_embeddings=32,
                     tensor_model_parallel_size=tp,
                     compute_dtype=jnp.float32, **kw)


def test_gpt_forward_and_loss_single_chip():
    model = GPTModel(_small_gpt())
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 16)))
    logits = jax.jit(model)(params, tokens)
    assert logits.shape == (2, 16, 128)
    loss = jax.jit(model.loss)(params, tokens, tokens)
    assert np.isfinite(float(loss))
    # untrained loss near ln(vocab)
    assert abs(float(loss) - np.log(128)) < 1.0


def test_gpt_trains():
    model = GPTModel(_small_gpt())
    params = model.init(jax.random.PRNGKey(1))
    opt = FusedAdam(lr=1e-3)
    state = opt.init(params)
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 128, (4, 16)))

    @jax.jit
    def step(params, state):
        loss, grads = jax.value_and_grad(model.loss)(params, tokens, tokens)
        params, state = opt.step(grads, state, params)
        return params, state, loss

    losses = []
    for _ in range(10):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5  # memorizing a fixed batch


def test_gpt_tp_matches_single_chip():
    """TP=2 sharded loss == TP=1 dense loss on the same weights
    (test_layers.py / gpt minimal parity model)."""
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2)
    try:
        m1, m2 = GPTModel(_small_gpt(tp=1)), GPTModel(_small_gpt(tp=2))
        p2 = m2.init(jax.random.PRNGKey(2))
        tokens = jnp.asarray(np.random.RandomState(2).randint(0, 128, (2, 16)))

        # explicit spec tree: tp-stacked leaves shard axis 0 (embedding word)
        # or axis 1 (per-layer stacks); everything else replicated
        specs = {
            "embedding": {"word": {"weight": P("tensor")},
                          "position": P()},
            "final_ln": {"weight": P(), "bias": P()},
            "layers": {
                "ln1": {"weight": P(), "bias": P()},
                "ln2": {"weight": P(), "bias": P()},
                "qkv": {"weight": P(None, "tensor"), "bias": P(None, "tensor")},
                "fc1": {"weight": P(None, "tensor"), "bias": P(None, "tensor")},
                "proj": {"weight": P(None, "tensor"), "bias": P(None, "tensor")},
                "fc2": {"weight": P(None, "tensor"), "bias": P(None, "tensor")},
            },
        }

        def tp_loss(p2, tokens):
            def inner(p2, tokens):
                return jax.lax.pmean(jax.lax.pmean(
                    m2.loss(p2, tokens, tokens), "tensor"), "data")
            return shard_map(inner, mesh=mesh, in_specs=(specs, P()),
                             out_specs=P())(p2, tokens)

        loss_tp = jax.jit(tp_loss)(p2, tokens)
        loss_dense = _dense_loss_from_sharded(m1, p2, tokens)
        np.testing.assert_allclose(float(loss_tp), float(loss_dense),
                                   rtol=2e-4)
    finally:
        parallel_state.destroy_model_parallel()


def _dense_loss_from_sharded(m1, p2, tokens):
    """Rebuild the tp=1 param layout from tp=2 stacked shards: column shards
    concatenate along out-features, row shards along in-features."""
    L = p2["layers"]

    def col_w(w):  # (L, 2, o/2, in) -> (L, 1, o, in)
        l, t, o, i = w.shape
        return w.reshape(l, 1, t * o, i)

    def col_b(b):  # (L, 2, o/2) -> (L, 1, o)
        l, t, o = b.shape
        return b.reshape(l, 1, t * o)

    def row_w(w):  # (L, 2, out, in/2) -> (L, 1, out, in)
        return jnp.concatenate([w[:, k] for k in range(w.shape[1])],
                               axis=-1)[:, None]

    p1 = {
        "embedding": {
            "word": {"weight": p2["embedding"]["word"]["weight"].reshape(
                1, 128, -1)},
            "position": p2["embedding"]["position"],
        },
        "final_ln": p2["final_ln"],
        "layers": {
            "ln1": L["ln1"], "ln2": L["ln2"],
            "qkv": {"weight": col_w(L["qkv"]["weight"]),
                    "bias": col_b(L["qkv"]["bias"])},
            "fc1": {"weight": col_w(L["fc1"]["weight"]),
                    "bias": col_b(L["fc1"]["bias"])},
            "proj": {"weight": row_w(L["proj"]["weight"]),
                     "bias": L["proj"]["bias"][:, :1]},
            "fc2": {"weight": row_w(L["fc2"]["weight"]),
                    "bias": L["fc2"]["bias"][:, :1]},
        },
    }
    return m1.loss(p1, tokens, tokens)


def test_bert_forward():
    cfg = BertConfig(vocab_size=128, hidden_size=64, num_layers=2,
                     num_attention_heads=4, max_position_embeddings=32,
                     compute_dtype=jnp.float32)
    model = BertModel(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.RandomState(3)
    tokens = jnp.asarray(rng.randint(0, 128, (2, 16)))
    ttypes = jnp.asarray(rng.randint(0, 2, (2, 16)))
    mask = jnp.asarray(rng.rand(2, 16) > 0.2, jnp.int32)
    logits = jax.jit(lambda p, t, tt, m: model(p, t, tt, m))(
        params, tokens, ttypes, mask)
    assert logits.shape == (2, 16, 128)
    h = model.encode(params, tokens, ttypes, mask)
    pooled = model.pool(params, h)
    assert pooled.shape == (2, 64)
    assert np.isfinite(np.asarray(pooled)).all()


def test_bert_padding_mask_matters():
    cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=1,
                     num_attention_heads=2, max_position_embeddings=16,
                     compute_dtype=jnp.float32)
    model = BertModel(cfg)
    params = model.init(jax.random.PRNGKey(4))
    tokens = jnp.asarray(np.random.RandomState(4).randint(0, 64, (1, 8)))
    full = model.encode(params, tokens, None, jnp.ones((1, 8), jnp.int32))
    half = model.encode(params, tokens, None,
                        jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]]))
    assert not np.allclose(np.asarray(full[:, 0]), np.asarray(half[:, 0]),
                           atol=1e-5)


def test_resnet50_forward_and_train_step():
    cfg = ResNetConfig(num_classes=10, compute_dtype=jnp.float32)
    model = ResNet50(cfg)
    params, state = model.init(jax.random.PRNGKey(5))
    x = jnp.asarray(np.random.RandomState(5).randn(2, 64, 64, 3), jnp.float32)
    logits, new_state = jax.jit(
        lambda p, s, x: model(p, s, x, training=True))(params, state, x)
    assert logits.shape == (2, 10)
    assert np.isfinite(np.asarray(logits)).all()
    # running stats updated
    assert int(new_state["stem"]["bn"].num_batches_tracked) == 1
    # eval path uses running stats
    logits_eval, st = jax.jit(
        lambda p, s, x: model(p, s, x, training=False))(params, state, x)
    assert int(st["stem"]["bn"].num_batches_tracked) == 0

    # one grad step decreases loss on a fixed batch
    labels = jnp.asarray([1, 3])
    from apex_tpu.optimizers import FusedSGD
    opt = FusedSGD(lr=0.005)
    ostate = opt.init(params)

    def loss_fn(params, state):
        logits, new_state = model(params, state, x, training=True)
        onehot = jax.nn.one_hot(labels, 10)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1)), new_state

    @jax.jit
    def step(params, state, ostate):
        (loss, new_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, state)
        params, ostate = opt.step(grads, ostate, params)
        return params, new_state, ostate, loss

    losses = []
    for _ in range(5):
        params, state, ostate, loss = step(params, state, ostate)
        losses.append(float(loss))
    # batch-2 BN makes per-step loss noisy; the optimizer must still make
    # progress below the initial loss at some point
    assert min(losses[1:]) < losses[0]


def test_bert_pretraining_loss_heads():
    """MLM head + binary head (standalone_bert BertLMHead /
    post_language_model_processing): masked-LM CE honors the loss mask,
    the binary head adds its CE, and grads reach both heads and the tied
    embedding."""
    cfg = BertConfig(vocab_size=128, hidden_size=64, num_layers=2,
                     num_attention_heads=4, max_position_embeddings=32,
                     compute_dtype=jnp.float32)
    model = BertModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 128, (2, 32)))
    labels = jnp.asarray(rng.randint(0, 128, (2, 32)))
    mask = jnp.asarray((rng.rand(2, 32) < 0.15).astype(np.float32))
    binary = jnp.asarray([0, 1])
    types = jnp.asarray(rng.randint(0, 2, (2, 32)))
    attn = jnp.ones((2, 32))

    loss = model.loss(params, tokens, labels, loss_mask=mask,
                      token_types=types, attention_mask=attn,
                      binary_labels=binary)
    assert np.isfinite(float(loss))
    lm_only = model.loss(params, tokens, labels, loss_mask=mask,
                         token_types=types, attention_mask=attn)
    assert float(loss) > float(lm_only)  # binary CE adds

    # loss mask: changing labels at masked-OUT positions changes nothing
    labels2 = jnp.where(mask > 0, labels, (labels + 1) % 128)
    np.testing.assert_allclose(
        float(model.loss(params, tokens, labels2, loss_mask=mask,
                         token_types=types, attention_mask=attn)),
        float(lm_only), rtol=1e-6)

    grads = jax.grad(lambda p: model.loss(
        p, tokens, labels, loss_mask=mask, token_types=types,
        attention_mask=attn, binary_labels=binary))(params)
    for path in ("lm_head", "binary_head"):
        assert any(float(np.abs(np.asarray(l)).max()) > 0
                   for l in jax.tree_util.tree_leaves(grads[path]))
    emb = np.asarray(grads["embedding"]["word"]["weight"])
    assert np.abs(emb).max() > 0


def test_bert_mlm_head_under_tp2():
    """Code-review r3: the MLM head must work under TP — vocab-sharded
    output bias and vocab-parallel CE (the all-reduce falls out of
    vocab_parallel_cross_entropy)."""
    from apex_tpu.transformer import parallel_state

    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2)
    try:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        cfg = BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                         num_attention_heads=4, max_position_embeddings=16,
                         compute_dtype=jnp.float32,
                         tensor_model_parallel_size=2, use_flash=False,
                         add_pooler=False, add_binary_head=True)
        model = BertModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        assert "binary_head" not in params  # gated on the pooler
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, 64, (2, 16)))
        labels = jnp.asarray(rng.randint(0, 64, (2, 16)))
        mask = jnp.ones((2, 16), jnp.float32)

        specs = {
            "embedding": {"word": {"weight": P("tensor")},
                          "position": P(), "tokentype": P()},
            "final_ln": {"weight": P(), "bias": P()},
            "layers": jax.tree_util.tree_map(
                lambda p: P(None, "tensor") if p.ndim >= 3 else P(),
                params["layers"]),
            "lm_head": {"dense": {"weight": P(), "bias": P()},
                        "ln": {"weight": P(), "bias": P()},
                        "bias": P("tensor")},
        }

        def run(params, tokens, labels, mask):
            def inner(params, tokens, labels, mask):
                return jax.lax.pmean(jax.lax.pmean(
                    model.loss(params, tokens, labels, loss_mask=mask),
                    "tensor"), "data")
            return shard_map(inner, mesh=mesh,
                             in_specs=(specs, P(), P(), P()),
                             out_specs=P())(params, tokens, labels, mask)

        loss = jax.jit(run)(params, tokens, labels, mask)
        assert np.isfinite(float(loss))
    finally:
        parallel_state.destroy_model_parallel()


def test_gpt_sequence_parallel_matches_tp():
    """Megatron-LM SP: sequence-sharded norms/residuals with gather/
    reduce-scatter TP boundaries must reproduce plain TP exactly (same
    params, same mesh)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer import parallel_state

    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2)
    try:
        kw = dict(vocab_size=128, hidden_size=32, num_layers=2,
                  num_attention_heads=4, max_position_embeddings=16,
                  compute_dtype=jnp.float32, use_flash=False,
                  tensor_model_parallel_size=2)
        m_tp = GPTModel(GPTConfig(**kw))
        m_sp = GPTModel(GPTConfig(**kw, sequence_parallel=True))
        params = m_tp.init(jax.random.PRNGKey(2))
        tokens = jnp.asarray(np.random.RandomState(2).randint(
            0, 128, (2, 16)))

        specs = {
            "embedding": {"word": {"weight": P("tensor")}, "position": P()},
            "final_ln": {"weight": P(), "bias": P()},
            "layers": jax.tree_util.tree_map(
                lambda p: P(None, "tensor") if p.ndim >= 3 else P(),
                params["layers"]),
        }

        def run(model, params, tokens):
            def inner(params, tokens):
                loss, grads = jax.value_and_grad(
                    lambda p: model.loss(p, tokens, tokens))(params)
                # SP: the LN custom_vjp already psums replicated-param
                # cotangents over the tensor axis (Megatron's separate
                # allreduce of sequence_parallel-marked params, moved into
                # the vjp); sp_grad_sync is a retained no-op.
                grads = model.sp_grad_sync(grads)
                pm = lambda v: jax.lax.pmean(
                    jax.lax.pmean(v, "tensor"), "data")
                return pm(loss), jax.tree_util.tree_map(pm, grads)
            return shard_map(inner, mesh=mesh, in_specs=(specs, P()),
                             out_specs=(P(), specs))(params, tokens)

        loss_tp, g_tp = jax.jit(
            lambda p, t: run(m_tp, p, t))(params, tokens)
        loss_sp, g_sp = jax.jit(
            lambda p, t: run(m_sp, p, t))(params, tokens)
        np.testing.assert_allclose(float(loss_sp), float(loss_tp),
                                   rtol=1e-6)
        for a, b in zip(jax.tree_util.tree_leaves(g_sp),
                        jax.tree_util.tree_leaves(g_tp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)
    finally:
        parallel_state.destroy_model_parallel()


def test_stem_space_to_depth_parity():
    """The conv0 space-to-depth reformulation is bit-equivalent math:
    fwd values, dW, and dX all match the plain 7x7/2 stem (the option is
    default-off by measurement — docs/PERF.md — but must stay correct)."""
    from apex_tpu.models import ResNet50, ResNetConfig

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 64, 64, 3), jnp.float32)
    w = jnp.asarray(rng.randn(7, 7, 3, 16) * 0.1, jnp.float32)
    plain = ResNet50(ResNetConfig(compute_dtype=jnp.float32,
                                  stem_space_to_depth=False))
    s2d = ResNet50(ResNetConfig(compute_dtype=jnp.float32,
                                stem_space_to_depth=True))
    np.testing.assert_allclose(np.asarray(plain._stem_conv(w, x)),
                               np.asarray(s2d._stem_conv(w, x)),
                               rtol=1e-5, atol=1e-5)
    gw_a = jax.grad(lambda w: jnp.sum(plain._stem_conv(w, x) ** 2))(w)
    gw_b = jax.grad(lambda w: jnp.sum(s2d._stem_conv(w, x) ** 2))(w)
    np.testing.assert_allclose(np.asarray(gw_a), np.asarray(gw_b),
                               rtol=1e-4, atol=1e-4)
    gx_a = jax.grad(lambda x: jnp.sum(plain._stem_conv(w, x) ** 2))(x)
    gx_b = jax.grad(lambda x: jnp.sum(s2d._stem_conv(w, x) ** 2))(x)
    np.testing.assert_allclose(np.asarray(gx_a), np.asarray(gx_b),
                               rtol=1e-4, atol=1e-4)

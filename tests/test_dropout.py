"""Dropout semantics tests (VERDICT r1 item 4).

- hidden/embedding dropout masks are IDENTICAL across TP ranks (replicated
  activations; the reference's default RNG stream), so a TP=4 run with
  hidden dropout matches the dense run with the same key;
- attention-probability dropout folds in the TP rank (sharded heads; the
  reference's tensor-parallel stream), so TP ranks draw independent masks;
- recompute under ``remat`` replays identical masks (keys are explicit
  inputs — the property CheckpointFunction stashes RNG state for in
  ``reference:apex/transformer/tensor_parallel/random.py:233-304``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.ops.dropout import dropout
from apex_tpu.transformer import parallel_state


@pytest.fixture
def mesh_tp4():
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=4)
    yield mesh
    parallel_state.destroy_model_parallel()


def _cfg(tp=1, **kw):
    return GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                     num_attention_heads=4, max_position_embeddings=32,
                     tensor_model_parallel_size=tp,
                     compute_dtype=jnp.float32, use_flash=False, **kw)


def _tokens(b=2, s=16, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 128, (b, s)))


def test_dropout_op_basics():
    x = jnp.ones((4, 100))
    key = jax.random.PRNGKey(0)
    y = dropout(x, 0.5, key)
    kept = np.asarray(y) != 0
    assert abs(kept.mean() - 0.5) < 0.1
    np.testing.assert_allclose(np.asarray(y)[kept], 2.0)  # inverted scaling
    np.testing.assert_array_equal(np.asarray(dropout(x, 0.5, None)),
                                  np.asarray(x))
    np.testing.assert_array_equal(
        np.asarray(dropout(x, 0.5, key, deterministic=True)), np.asarray(x))


def test_gpt_dropout_changes_loss_and_is_deterministic():
    model = GPTModel(_cfg(hidden_dropout=0.2, attention_dropout=0.1))
    params = model.init(jax.random.PRNGKey(0))
    toks = _tokens()
    rng = jax.random.PRNGKey(42)
    l_eval = model.loss(params, toks, toks)
    l1 = model.loss(params, toks, toks, dropout_rng=rng)
    l2 = model.loss(params, toks, toks, dropout_rng=rng)
    l3 = model.loss(params, toks, toks, dropout_rng=jax.random.PRNGKey(43))
    assert float(l1) == float(l2)            # same key, same masks
    assert float(l1) != float(l_eval)        # dropout actually fires
    assert float(l1) != float(l3)            # key-dependent




def _tp_specs():
    specs = {
        "embedding": {"word": {"weight": P("tensor")}, "position": P()},
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
    return specs

def test_hidden_dropout_tp_matches_dense(mesh_tp4):
    """With attention_dropout=0, hidden+embedding dropout draws only from
    the TP-replicated stream: the TP=4 loss equals the dense loss with the
    same key (mask identity across ranks, reference random.py:200-230)."""
    mesh = parallel_state.get_mesh()
    toks = _tokens()
    rng = jax.random.PRNGKey(7)

    dense = GPTModel(_cfg(hidden_dropout=0.3))
    params = dense.init(jax.random.PRNGKey(0))
    l_dense = dense.loss(params, toks, toks, dropout_rng=rng)

    tp_model = GPTModel(_cfg(tp=4, hidden_dropout=0.3))
    tp_params = tp_model.init(jax.random.PRNGKey(0))

    def run(tp_params, toks):
        def inner(tp_params, toks):
            l = tp_model.loss(tp_params, toks, toks, dropout_rng=rng)
            return jax.lax.pmean(l, "tensor")
        return shard_map(inner, mesh=mesh, in_specs=(_tp_specs(), P()),
                         out_specs=P())(tp_params, toks)

    l_tp = jax.jit(run)(tp_params, toks)
    np.testing.assert_allclose(float(l_tp), float(l_dense), rtol=2e-5)


def test_attention_dropout_tp_rank_streams(mesh_tp4):
    """Attention dropout folds in the TP rank, so the TP result differs from
    the dense run with the same key (independent masks per head shard) but
    stays deterministic."""
    mesh = parallel_state.get_mesh()
    toks = _tokens()
    rng = jax.random.PRNGKey(7)

    dense = GPTModel(_cfg(attention_dropout=0.4))
    params = dense.init(jax.random.PRNGKey(0))
    l_dense = dense.loss(params, toks, toks, dropout_rng=rng)

    tp_model = GPTModel(_cfg(tp=4, attention_dropout=0.4))
    tp_params = tp_model.init(jax.random.PRNGKey(0))

    def run(tp_params, toks):
        def inner(tp_params, toks):
            l = tp_model.loss(tp_params, toks, toks, dropout_rng=rng)
            return jax.lax.pmean(l, "tensor")
        return shard_map(inner, mesh=mesh, in_specs=(_tp_specs(), P()),
                         out_specs=P())(tp_params, toks)

    l_tp1 = jax.jit(run)(tp_params, toks)
    l_tp2 = jax.jit(run)(tp_params, toks)
    assert float(l_tp1) == float(l_tp2)      # deterministic
    assert float(l_tp1) != float(l_dense)    # rank-folded masks differ


def test_remat_replays_dropout_masks():
    """remat recomputes the forward in backward; explicit keys make the
    recomputed dropout masks identical, so loss AND grads match the
    non-remat run exactly."""
    toks = _tokens()
    rng = jax.random.PRNGKey(11)
    losses, grads = [], []
    for remat in (False, True):
        model = GPTModel(_cfg(hidden_dropout=0.2, attention_dropout=0.1,
                              remat=remat))
        params = model.init(jax.random.PRNGKey(0))
        l, g = jax.value_and_grad(
            lambda p: model.loss(p, toks, toks, dropout_rng=rng))(params)
        losses.append(float(l))
        grads.append(g)
    assert losses[0] == losses[1]
    for a, b in zip(jax.tree_util.tree_leaves(grads[0]),
                    jax.tree_util.tree_leaves(grads[1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_flash_kernel_dropout_in_model():
    """The Pallas in-kernel dropout path wires through GPT (shapes eligible
    for flash) and matches the XLA fallback with the same seed."""
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=1,
                    num_attention_heads=1, max_position_embeddings=128,
                    compute_dtype=jnp.float32, attention_dropout=0.3,
                    use_flash=True)
    cfg_ref = dataclasses_replace(cfg, use_flash=False)
    toks = _tokens(b=1, s=128)
    rng = jax.random.PRNGKey(5)
    m1, m2 = GPTModel(cfg), GPTModel(cfg_ref)
    params = m1.init(jax.random.PRNGKey(0))
    l_pallas = m1.loss(params, toks, toks, dropout_rng=rng)
    l_ref = m2.loss(params, toks, toks, dropout_rng=rng)
    np.testing.assert_allclose(float(l_pallas), float(l_ref), rtol=2e-5)


def dataclasses_replace(cfg, **kw):
    import dataclasses
    return dataclasses.replace(cfg, **kw)

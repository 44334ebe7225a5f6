"""``ops.mlp.gelu_tanh``: ``jax.nn.gelu(approximate=True)`` whose backward
keeps its input alone.

The forward is the same ops and so the same bits; the gradient is the
derivative of that function, computed in float32 from ``x`` and rounded
once. What it buys is counted by the residuals AD saves: the GPT MLP
keeps two arrays of the fc1 output's shape (the GELU's input and its
output, which fc2's weight gradient reads) where the plain expression kept
six.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.ops import mlp
from apex_tpu.ops.mlp import fused_dense_gelu_dense, gelu_tanh

DTYPES = [jnp.float32, jnp.bfloat16]
# |x| past 5 on both sides, 0 itself, and the derivative's root near -0.75
X = np.concatenate([np.linspace(-12.0, 12.0, 40001),
                    [0.0, -0.0, 30.0, -30.0]])


def _plain(x):
    return jax.nn.gelu(x, approximate=True)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint16 if a.dtype.itemsize == 2 else np.uint32)


def _spacing(scale, dtype):
    """The dtype's spacing of values at ``scale``."""
    mantissa = 7 if dtype == jnp.bfloat16 else 23
    return 2.0 ** (np.floor(np.log2(scale)) - mantissa)


def _within(got, want, dtype):
    """``|got - want|`` in units of the dtype's spacing at ``want``, or at
    1/4 where ``want`` is smaller: the derivative crosses zero near
    x = -0.75, where its two terms cancel, and past |x| ~ 5 it is 0 or 1
    less a few ulps, so a spacing AT a tiny value says nothing of it."""
    return np.abs(np.asarray(got, np.float64) - want) / _spacing(
        np.maximum(np.abs(want), 0.25), dtype)


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_forward_is_jax_gelu_bit_for_bit(dtype, jit):
    x = jnp.asarray(X, dtype)
    wrap = jax.jit if jit else (lambda f: f)
    np.testing.assert_array_equal(_bits(wrap(gelu_tanh)(x)),
                                  _bits(wrap(_plain)(x)))


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_gradient_is_jax_grad_of_the_float32_gelu(dtype):
    """Held to ``jax.grad`` of ``jax.nn.gelu(approximate=True)`` in float32
    at the same inputs: 1e-6 relative in float32 (8 ulps), one bfloat16
    ulp in bfloat16 (``jax.grad`` of the bfloat16 expression rounds every
    intermediate and is no reference there)."""
    x = jnp.asarray(X, dtype)
    grad = jax.jit(jax.grad(
        lambda v: jnp.sum(gelu_tanh(v).astype(jnp.float32))))
    want = np.asarray(jax.jit(jax.grad(lambda v: jnp.sum(_plain(v))))(
        x.astype(jnp.float32)), np.float64)
    got = grad(x)
    assert got.dtype == dtype
    off = _within(got, want, dtype)
    limit = 8.0 if dtype == jnp.float32 else 1.0
    assert off.max() <= limit, (off.max(), X[off.argmax()])


def _mlp_residuals(policy):
    model = GPTModel(GPTConfig(
        vocab_size=64, hidden_size=32, num_layers=2, num_attention_heads=4,
        max_position_embeddings=16, remat_policy=policy))
    params = model.init(jax.random.PRNGKey(0))
    lp = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32), jnp.bfloat16)
    fn = model.remat_policy.wrap(
        lambda lp, x: jnp.sum(model._mlp(lp, x).astype(jnp.float32)))
    return [(aval, why) for aval, why in saved_residuals(fn, lp, x)
            if aval.shape == (2, 8, 128)]


@pytest.mark.parametrize("policy,saved", [
    ("none", 2), ("selective", 1), ("offload", 1), ("full", 0)])
def test_the_mlp_saves_the_gelu_input_alone(policy, saved):
    """Without remat: the fc1 output (the GELU's input) and the GELU's
    output, which fc2's weight gradient reads (the plain expression saved
    four more: tanh, 1 - tanh, 0.5 (1 + tanh), 3 x^2). The name-based
    policies keep the tagged pre-GELU tensor, as before; ``full`` saves
    none."""
    res = _mlp_residuals(policy)
    assert len(res) == saved, res
    if policy == "none":
        assert all(aval.dtype == jnp.bfloat16 for aval, _ in res)
    if policy == "selective":
        assert "tag" in res[0][1], res


def _fused_plain(x, w1, b1, w2, b2):
    # the expression fused_dense_gelu_dense had before gelu_tanh
    h = jax.nn.gelu(mlp._dense(x, w1, b1), approximate=True)
    return mlp._dense(h.astype(x.dtype), w2, b2).astype(x.dtype)


@pytest.mark.parametrize("dtype", DTYPES, ids=lambda d: d.__name__)
def test_fused_dense_gelu_dense_gradients_are_unchanged(dtype):
    """Its GELU runs on the float32 accumulator whatever the input dtype,
    so every gradient equals the plain expression's to 8 float32 ulps of
    the leaf's largest value, or within one ulp of it in bfloat16."""
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(keys[0], (16, 64), jnp.float32) * 2.0
    w1 = jax.random.normal(keys[1], (256, 64)) / 8.0
    b1 = jax.random.normal(keys[2], (256,))
    w2 = jax.random.normal(keys[3], (32, 256)) / 16.0
    b2 = jax.random.normal(keys[4], (32,))
    dy = jax.random.normal(keys[5], (16, 32))
    args = [a.astype(dtype) for a in (x, w1, b1, w2, b2)]

    def grads(f):
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(f(*a).astype(jnp.float32) * dy),
            argnums=tuple(range(5))))(*args)

    for got, want in zip(grads(fused_dense_gelu_dense), grads(_fused_plain)):
        want = np.asarray(want, np.float64)
        off = np.abs(np.asarray(got, np.float64) - want) / _spacing(
            np.abs(want).max(), dtype)
        limit = 8.0 if dtype == jnp.float32 else 1.0
        assert off.max() <= limit, off.max()

"""Fused MLP / dense layers.

Reference: ``reference:apex/mlp/mlp.py:8-79`` (whole-MLP fused fwd/bwd over
``csrc/mlp_cuda.cu`` cuBLAS GEMMs + fused bias/activation kernels) and
``reference:apex/fused_dense/fused_dense.py:53-86`` (cuBLASLt epilogue GEMMs:
linear+bias, linear+bias+GELU+linear+bias).

On TPU every GEMM+bias+activation chain is one XLA fusion feeding the MXU —
the hand-fused kernels' entire purpose is already met by the compiler, so
these are thin functional modules that (a) keep the reference API surface,
(b) pin ``preferred_element_type=float32`` so bf16 inputs accumulate in fp32
on the MXU like the CUDA kernels accumulate in fp32, and (c) initialize
exactly like the reference (uniform ±1/sqrt(fan_in), ``mlp.py:41-46``).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = ["MLP", "FusedDense", "FusedDenseGeluDense", "mlp_forward",
           "fused_dense", "fused_dense_gelu_dense", "gelu_tanh"]

_ACTIVATIONS = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
}


@jax.custom_vjp
def gelu_tanh(x):
    """``jax.nn.gelu(x, approximate=True)``, the same ops and so the same
    bits, whose backward keeps ``x`` ALONE. AD of the plain expression
    saves ``x``, ``tanh(u)``, ``1 - tanh(u)``, ``0.5 (1 + tanh(u))`` and
    ``3 x^2`` beside the output; in a scanned layer stack each is a stacked
    activation written by the forward and read back by the backward. The
    backward here recomputes them from ``x`` in float32 (a few VPU/EUP
    operations a value, inside a fusion bound by the bytes it reads) and
    rounds the gradient to ``x.dtype`` once."""
    return jax.nn.gelu(x, approximate=True)


def _gelu_tanh_fwd(x):
    return jax.nn.gelu(x, approximate=True), x


def _gelu_tanh_bwd(x, g):
    # the derivative of the function the forward computes: its two
    # constants rounded to x's dtype, as jax.nn.gelu rounds them
    f32 = jnp.float32
    c = jnp.asarray(math.sqrt(2.0 / math.pi), x.dtype).astype(f32)
    a = jnp.asarray(0.044715, x.dtype).astype(f32)
    xf = x.astype(f32)
    t = jnp.tanh(c * (xf + a * xf ** 3))
    d = 0.5 * (1.0 + t) + 0.5 * xf * (1.0 + t) * (1.0 - t) * c * (
        1.0 + 3.0 * a * xf * xf)
    return ((g.astype(f32) * d).astype(x.dtype),)


gelu_tanh.defvjp(_gelu_tanh_fwd, _gelu_tanh_bwd)


def _dense(x, w, b):
    # w stored (out, in) like torch; MXU matmul with fp32 accumulation
    y = jax.lax.dot_general(x, w, (((x.ndim - 1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y


def mlp_forward(params: Sequence[Tuple[jnp.ndarray, Optional[jnp.ndarray]]],
                x: jnp.ndarray, activation: str = "relu") -> jnp.ndarray:
    """Chain of (weight, bias) pairs with ``activation`` between layers and
    after the last layer — matching ``MlpFunction``'s behavior of applying
    the activation to every layer output (``reference:csrc/mlp_cuda.cu:437-659``)."""
    act = _ACTIVATIONS[activation]
    y = x
    for w, b in params:
        y = act(_dense(y, w, b)).astype(x.dtype)
    return y


class MLP:
    """``apex.mlp.MLP(mlp_sizes, bias=True, relu=True, activation='relu')``
    (``reference:apex/mlp/mlp.py:26-79``)."""

    def __init__(self, mlp_sizes: Sequence[int], bias: bool = True,
                 activation: str = "relu", param_dtype=jnp.float32):
        if len(mlp_sizes) < 2:
            raise ValueError("mlp_sizes must have at least 2 entries")
        if activation not in _ACTIVATIONS:
            raise ValueError(f"activation must be one of {list(_ACTIVATIONS)}")
        self.mlp_sizes = tuple(int(s) for s in mlp_sizes)
        self.bias = bias
        self.activation = activation
        self.param_dtype = param_dtype

    def init(self, key: jax.Array) -> list:
        """Uniform ±1/sqrt(fan_in) for weights and biases
        (``reference:apex/mlp/mlp.py:41-46`` reset_parameters)."""
        params = []
        for i in range(len(self.mlp_sizes) - 1):
            fan_in, fan_out = self.mlp_sizes[i], self.mlp_sizes[i + 1]
            key, wk, bk = jax.random.split(key, 3)
            bound = 1.0 / math.sqrt(fan_in)
            w = jax.random.uniform(wk, (fan_out, fan_in), self.param_dtype,
                                   -bound, bound)
            b = (jax.random.uniform(bk, (fan_out,), self.param_dtype,
                                    -bound, bound) if self.bias else None)
            params.append((w, b))
        return params

    def __call__(self, params, x):
        return mlp_forward(params, x, self.activation)


def fused_dense(x, weight, bias):
    """``fused_dense_cuda.linear_bias_forward`` — GEMM + bias epilogue."""
    return _dense(x, weight, bias).astype(x.dtype)


def fused_dense_gelu_dense(x, w1, b1, w2, b2):
    """``fused_dense_cuda.linear_gelu_linear_forward``: GEMM+bias+GELU+GEMM+bias
    in one fusion (tanh GELU, matching cuBLASLt's CUBLASLT_EPILOGUE_GELU)."""
    h = gelu_tanh(_dense(x, w1, b1))
    return _dense(h.astype(x.dtype), w2, b2).astype(x.dtype)


class FusedDense:
    """``reference:apex/fused_dense/fused_dense.py:53-67``."""

    def __init__(self, in_features: int, out_features: int, bias: bool = True,
                 param_dtype=jnp.float32):
        self.in_features, self.out_features = in_features, out_features
        self.bias = bias
        self.param_dtype = param_dtype

    def init(self, key: jax.Array) -> dict:
        bound = 1.0 / math.sqrt(self.in_features)
        key, wk, bk = jax.random.split(key, 3)
        p = {"weight": jax.random.uniform(
            wk, (self.out_features, self.in_features), self.param_dtype,
            -bound, bound)}
        if self.bias:
            p["bias"] = jax.random.uniform(bk, (self.out_features,),
                                           self.param_dtype, -bound, bound)
        return p

    def __call__(self, params, x):
        return fused_dense(x, params["weight"], params.get("bias"))


class FusedDenseGeluDense:
    """``reference:apex/fused_dense/fused_dense.py:71-86``."""

    def __init__(self, in_features: int, intermediate_features: int,
                 out_features: int, bias: bool = True, param_dtype=jnp.float32):
        if not bias:
            raise ValueError("FusedDenseGeluDense requires bias=True "
                             "(as in the reference)")
        self.d1 = FusedDense(in_features, intermediate_features, True, param_dtype)
        self.d2 = FusedDense(intermediate_features, out_features, True, param_dtype)

    def init(self, key: jax.Array) -> dict:
        k1, k2 = jax.random.split(key)
        return {"dense1": self.d1.init(k1), "dense2": self.d2.init(k2)}

    def __call__(self, params, x):
        return fused_dense_gelu_dense(
            x, params["dense1"]["weight"], params["dense1"]["bias"],
            params["dense2"]["weight"], params["dense2"]["bias"])

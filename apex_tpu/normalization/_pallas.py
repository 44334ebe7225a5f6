"""Pallas TPU kernels for fused LayerNorm / RMSNorm forward + backward.

TPU re-design of ``reference:csrc/layer_norm_cuda_kernel.cu`` (Welford row
stats at :12-178, apply at :353-412, grads at :540-678) and the
``fast_layer_norm`` contrib kernels (``reference:apex/contrib/csrc/layer_norm/``,
hidden sizes to 64k). One grid row-block per program: stats are an in-VMEM
row reduction in fp32 (a single-pass mean/variance is numerically fine in
fp32 VMEM — Welford's streaming update exists to avoid multi-pass HBM reads,
which don't happen here), normalize + affine fuse into the same VMEM pass.
Backward emits per-block partial dgamma/dbeta tiles that the caller sums —
the TPU analog of the two-stage part-grad reduction in
``layer_norm_cuda_kernel.cu:540-678``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.backend import pallas_interpret

__all__ = ["ln_fwd", "ln_bwd", "supports_pallas"]

_VMEM_BUDGET = 8 * 1024 * 1024  # conservative half of ~16MB VMEM


def _block_rows(n_rows: int, hidden: int) -> int:
    # ~5 fp32 row-buffers of width `hidden` live at once; keep under budget.
    # Mosaic requires the row-block to be a multiple of 8 (fp32 sublane
    # tile) or the full array, so the choices are: whole array if it fits,
    # else the largest multiple of 8 under budget that divides n_rows.
    per_row = hidden * 4 * 5
    cap = max(1, _VMEM_BUDGET // per_row)
    if n_rows <= cap:
        return n_rows
    rows = (min(n_rows, cap) // 8) * 8
    while rows >= 8 and n_rows % rows:
        rows -= 8
    if rows < 8:
        # no feasible block under budget (cap < 8, or nothing divides
        # n_rows): falling back to the whole array would blow the VMEM
        # budget this function exists to enforce — refuse loudly instead
        # (supports_pallas screens these shapes for the auto path)
        raise ValueError(
            f"no VMEM-feasible Pallas row block for rows={n_rows}, "
            f"hidden={hidden}; pass use_pallas=False")
    return rows


def prefer_pallas(n_rows: int, hidden: int) -> bool:
    """Auto-selection policy (capability is :func:`supports_pallas`; this is
    *preference*). Measured on v5e, bf16 fwd+bwd, 200-iteration device
    loops (round 5; pallas_ms vs xla_ms at constant 32M elements):

    ========  =========  ======  ======
    hidden    rows       Pallas  XLA
    ========  =========  ======  ======
    4096      8192       1.01    0.81
    8192      4096       1.19    0.65
    16384     2048       1.00    0.83
    32768     1024       1.14    0.72
    ========  =========  ======  ======

    XLA's native LN lowering wins at EVERY hidden size this kernel
    supports — its fusion into neighboring ops beats what a custom_vjp
    kernel-call boundary allows, including the large-hidden regime the
    reference's ``fast_layer_norm`` exists for
    (``reference:apex/contrib/csrc/layer_norm/ln_api.cpp:246``): on TPU
    the compiler's row reduction simply does not degrade the way the CUDA
    baseline's did. The measured answer is therefore *never* — the kernel
    is retained as the independent parity reference and for explicit
    ``use_pallas=True`` opt-in."""
    return False


def supports_pallas(n_rows: int, hidden: int) -> bool:
    """Kernel eligibility — the analog of ``is_kernel_available``
    (``reference:apex/transformer/functional/fused_softmax.py:159-179``)."""
    if jax.default_backend() != "tpu":
        return False
    if hidden % 128 or hidden * 4 * 5 > _VMEM_BUDGET:
        return False
    # a feasible block must exist: the whole array under budget, or an
    # 8-row-multiple tiling (which further requires >= 8 rows of budget —
    # at hidden >~ 52k the 8-row block itself exceeds it, see _block_rows)
    per_row = hidden * 4 * 5
    cap = _VMEM_BUDGET // per_row
    return n_rows <= cap or (cap >= 8 and n_rows % 8 == 0)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-manual-axes of ``like`` (see
    the flash-attention twin: pallas_call under shard_map needs it)."""
    from apex_tpu.utils.vma import leaf_vma
    vma = leaf_vma(like)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _stats(xf: jnp.ndarray, eps: float, rms: bool):
    if rms:
        ms = jnp.mean(xf * xf, axis=1, keepdims=True)
        invvar = jax.lax.rsqrt(ms + eps)
        return jnp.zeros_like(invvar), invvar, xf * invvar
    mean = jnp.mean(xf, axis=1, keepdims=True)
    centered = xf - mean
    var = jnp.mean(centered * centered, axis=1, keepdims=True)
    invvar = jax.lax.rsqrt(var + eps)
    return mean, invvar, centered * invvar


def _fwd_body(x_ref, w_ref, b_ref, o_ref, mean_ref, invvar_ref,
              eps: float, rms: bool):
    mean, invvar, xhat = _stats(x_ref[:].astype(jnp.float32), eps, rms)
    out = xhat
    if w_ref is not None:
        out = out * w_ref[:].astype(jnp.float32)
    if b_ref is not None:
        out = out + b_ref[:].astype(jnp.float32)
    o_ref[:] = out.astype(o_ref.dtype)
    mean_ref[:] = mean
    invvar_ref[:] = invvar


def _bwd_body(dy_ref, x_ref, mean_ref, invvar_ref, w_ref,
              dx_ref, dw_ref, db_ref, rms: bool):
    dy = dy_ref[:].astype(jnp.float32)
    xf = x_ref[:].astype(jnp.float32)
    invvar = invvar_ref[:]
    xhat = xf * invvar if rms else (xf - mean_ref[:]) * invvar
    dxhat = dy * w_ref[:].astype(jnp.float32) if w_ref is not None else dy
    # dx = invvar*(dxhat - mean(dxhat) - xhat*mean(dxhat*xhat))   [LN]
    # dx = invvar*(dxhat - xhat*mean(dxhat*xhat))                 [RMS]
    m2 = jnp.mean(dxhat * xhat, axis=1, keepdims=True)
    if rms:
        dx = invvar * (dxhat - xhat * m2)
    else:
        m1 = jnp.mean(dxhat, axis=1, keepdims=True)
        dx = invvar * (dxhat - m1 - xhat * m2)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    # dgamma/dbeta accumulate across the sequential grid into one resident
    # (1, h) VMEM block (constant index_map) — the TPU analog of the
    # two-stage part-grad reduction in layer_norm_cuda_kernel.cu:540-678,
    # with stage 2 done by Mosaic's revisit-in-VMEM rule instead of a
    # second kernel.
    first = pl.program_id(0) == 0
    if dw_ref is not None:
        part_w = jnp.sum(dy * xhat, axis=0, keepdims=True)

        @pl.when(first)
        def _():
            dw_ref[:] = jnp.zeros_like(dw_ref)

        dw_ref[:] += part_w
    if db_ref is not None:
        part_b = jnp.sum(dy, axis=0, keepdims=True)

        @pl.when(first)
        def _():
            db_ref[:] = jnp.zeros_like(db_ref)

        db_ref[:] += part_b


def ln_fwd(x2d: jnp.ndarray, weight: Optional[jnp.ndarray],
           bias: Optional[jnp.ndarray], *, eps: float, rms: bool,
           out_dtype) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Returns ``(out, mean, invvar)``; mean/invvar are ``(rows, 1)`` fp32
    (the saved stats of ``reference:apex/normalization/fused_layer_norm.py:32-56``)."""
    n, h = x2d.shape
    has_w, has_b = weight is not None, bias is not None
    br = _block_rows(n, h)
    row_spec = pl.BlockSpec((br, h), lambda i: (i, 0), memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)

    in_specs, args = [row_spec], [x2d]
    if has_w:
        in_specs.append(w_spec)
        args.append(weight.reshape(1, h))
    if has_b:
        in_specs.append(w_spec)
        args.append(bias.reshape(1, h))

    def kernel(x_ref, *refs):
        i = 0
        w_ref = refs[i] if has_w else None
        i += has_w
        b_ref = refs[i] if has_b else None
        i += has_b
        _fwd_body(x_ref, w_ref, b_ref, *refs[i:], eps=eps, rms=rms)

    return pl.pallas_call(
        kernel,
        grid=(n // br,),
        interpret=pallas_interpret(),
        in_specs=in_specs,
        out_specs=(row_spec, stat_spec, stat_spec),
        out_shape=(
            _sds((n, h), out_dtype, x2d),
            _sds((n, 1), jnp.float32, x2d),
            _sds((n, 1), jnp.float32, x2d),
        ),
    )(*args)


def ln_bwd(dy2d: jnp.ndarray, x2d: jnp.ndarray, mean: jnp.ndarray,
           invvar: jnp.ndarray, weight: Optional[jnp.ndarray], *,
           rms: bool, has_bias: bool, x_dtype, w_dtype):
    """Returns ``(dx, dweight, dbias)``; dweight/dbias ``None`` when absent."""
    n, h = x2d.shape
    has_w = weight is not None
    br = _block_rows(n, h)
    grid_n = n // br
    row_spec = pl.BlockSpec((br, h), lambda i: (i, 0), memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((br, 1), lambda i: (i, 0), memory_space=pltpu.VMEM)
    w_spec = pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)
    # dgamma/dbeta: one (1, h) block revisited by every program (see
    # _bwd_body's accumulation)
    acc_spec = pl.BlockSpec((1, h), lambda i: (0, 0), memory_space=pltpu.VMEM)

    in_specs = [row_spec, row_spec, stat_spec, stat_spec]
    args = [dy2d, x2d, mean, invvar]
    if has_w:
        in_specs.append(w_spec)
        args.append(weight.reshape(1, h))

    out_specs = [row_spec]
    out_shape = [_sds((n, h), x_dtype, x2d)]
    if has_w:
        out_specs.append(acc_spec)
        out_shape.append(_sds((1, h), jnp.float32, x2d))
    if has_bias:
        out_specs.append(acc_spec)
        out_shape.append(_sds((1, h), jnp.float32, x2d))

    def kernel(dy_ref, x_ref, mean_ref, invvar_ref, *refs):
        i = 0
        w_ref = refs[i] if has_w else None
        i += has_w
        dx_ref = refs[i]
        i += 1
        dw_ref = refs[i] if has_w else None
        i += has_w
        db_ref = refs[i] if has_bias else None
        _bwd_body(dy_ref, x_ref, mean_ref, invvar_ref, w_ref,
                  dx_ref, dw_ref, db_ref, rms=rms)

    res = pl.pallas_call(
        kernel, grid=(grid_n,),
        in_specs=in_specs, out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=pallas_interpret(),
    )(*args)
    if not isinstance(res, (tuple, list)):
        res = (res,)
    dx = res[0]
    dw = res[1][0].astype(w_dtype) if has_w else None
    db = res[-1][0].astype(w_dtype) if has_bias else None
    return dx, dw, db

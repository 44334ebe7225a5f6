"""Mamba-2 (state-space duality) pieces for serving: the chunked scan of a
prefill, the one-token update of a decode step, the causal depthwise conv
in front of both.

The recurrence of one head (``P`` channels, state ``(P, N)``, float32):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t        y_t = h_t C_t

``A < 0`` a head, ``dt_t > 0`` a head and token, ``B_t``, ``C_t`` ``(N,)``
shared by the heads of one group. Token by token that is ``T`` dependent
steps (:func:`mamba2_recurrence`, the oracle). The CHUNKED form cuts the
sequence into chunks of ``Q`` tokens: with ``cs_t`` the running sum of
``dt A`` inside a chunk,

    y_t = sum_{s <= t} (C_t . B_s) exp(cs_t - cs_s) dt_s x_s    (in the chunk:
                                    a decay-masked (Q, Q) product on the MXU)
        + exp(cs_t) C_t . h_prev                          (the carried state)
    h_next = exp(cs_Q) h_prev + sum_s exp(cs_Q - cs_s) dt_s x_s (x) B_s

so only ``T / Q`` steps depend on one another. :func:`mamba2_chunk_scan` is
that algorithm twice: a Pallas kernel named ``mamba2_chunk_scan`` (grid over
the chunks in order, the state resident in VMEM, every product and decay
float32) and the same in XLA ops, behind the kernels' usual gate
(``use_pallas=None``: the kernel where the shapes sit on the tiles).

Positions at or past ``length`` are padding: their ``dt`` is taken as 0, so
they decay nothing and add nothing and the state that comes back is the last
REAL token's.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.backend import pallas_interpret as _interp

__all__ = ["mamba2_chunk_scan", "mamba2_recurrence", "mamba2_decode_update",
           "causal_conv", "causal_conv_update", "supports_chunk_scan"]

_HI = jax.lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))     # a @ b.T
_TN = (((0,), (0,)), ((), ()))     # a.T @ b
_VMEM_LIMIT = 64 * 1024 * 1024


def supports_chunk_scan(tokens: int, chunk: int, state: int) -> bool:
    """Whether the kernel's blocks sit on the (8, 128) tiles: whole chunks
    of a multiple of 128 tokens, a state of a multiple of 128 lanes."""
    return tokens % chunk == 0 and chunk % 128 == 0 and state % 128 == 0


# -- the conv in front --------------------------------------------------------


def causal_conv(x, weight, bias, length=None):
    """Depthwise causal conv then SiLU over one sequence: ``x`` ``(T,
    channels)``, ``weight`` ``(channels, K)``, ``out_t = silu(b + sum_j
    w_j x_{t-K+1+j})`` with zeros before the sequence. Returns ``(out (T,
    channels) in x.dtype, tail (K - 1, channels))``: the inputs of the last
    ``K - 1`` positions before ``length`` (``T`` when None), zeros where the
    sequence is shorter — what the next token's conv reads."""
    T, K = x.shape[0], weight.shape[1]
    padded = jnp.concatenate(
        [jnp.zeros((K - 1, x.shape[1]), x.dtype), x], axis=0)
    w = weight.astype(jnp.float32)
    acc = bias.astype(jnp.float32)[None, :]
    for j in range(K):
        acc = acc + padded[j:j + T].astype(jnp.float32) * w[None, :, j]
    end = T if length is None else length
    tail = jax.lax.dynamic_slice_in_dim(padded, end, K - 1, 0)
    return jax.nn.silu(acc).astype(x.dtype), tail


def causal_conv_update(tail, x_new, weight, bias):
    """One token a row: ``tail`` ``(S, K - 1, channels)`` the inputs before
    it, ``x_new`` ``(S, channels)``. Returns ``(out (S, channels), the tail
    moved on by one)``."""
    window = jnp.concatenate([tail, x_new[:, None, :].astype(tail.dtype)],
                             axis=1)
    out = jnp.einsum("skc,ck->sc", window.astype(jnp.float32),
                     weight.astype(jnp.float32)) \
        + bias.astype(jnp.float32)[None, :]
    return jax.nn.silu(out).astype(x_new.dtype), window[:, 1:]


# -- token by token -----------------------------------------------------------


def _per_head(bc, heads):
    """``(..., G, N)`` -> ``(..., H, N)``: head ``i`` reads group ``i //
    (H / G)``."""
    return jnp.repeat(bc, heads // bc.shape[-2], axis=-2)


def mamba2_recurrence(x, dt, A, B, C, length=None):
    """The recurrence as written, one token a step: the oracle of the
    chunked forms. Shapes as :func:`mamba2_chunk_scan`; float32."""
    T, H, _ = x.shape
    if length is not None:
        dt = jnp.where((jnp.arange(T) < length)[:, None], dt, 0.0)
    Bh = _per_head(B.astype(jnp.float32), H)
    Ch = _per_head(C.astype(jnp.float32), H)

    def step(h, row):
        x_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    h0 = jnp.zeros(x.shape[1:] + (B.shape[-1],), jnp.float32)
    h, y = jax.lax.scan(step, h0, (x.astype(jnp.float32),
                                   dt.astype(jnp.float32), Bh, Ch))
    return y, h


def mamba2_decode_update(state, x, dt, A, B, C, valid=None):
    """One token a slot: ``state`` ``(S, H, P, N)`` float32, ``x`` ``(S, H,
    P)``, ``dt`` ``(S, H)``, ``B``, ``C`` ``(S, G, N)``. Returns ``(y (S,
    H, P) float32, the new state)``; a slot outside ``valid`` keeps its
    state as it was."""
    H = x.shape[1]
    dt = dt.astype(jnp.float32)
    Bh = _per_head(B.astype(jnp.float32), H)
    Ch = _per_head(C.astype(jnp.float32), H)
    new = jnp.exp(dt * A)[:, :, None, None] * state \
        + (dt[:, :, None] * x.astype(jnp.float32))[..., None] \
        * Bh[:, :, None, :]
    y = jnp.sum(new * Ch[:, :, None, :], axis=-1)
    if valid is not None:
        new = jnp.where(valid[:, None, None, None], new, state)
    return y, new


# -- chunked ------------------------------------------------------------------


def _chunk_sums(dt, A, chunk):
    """``cs (T, H)``: the running sum of ``dt A`` inside each chunk."""
    T, H = dt.shape
    a = (dt * A[None, :]).reshape(T // chunk, chunk, H)
    return jnp.cumsum(a, axis=1).reshape(T, H)


def _chunk_scan_xla(x, dt, cs, B, C, chunk):
    T, H, P = x.shape
    G, N = B.shape[1:]
    n, Q = T // chunk, chunk
    xdt = (x.astype(jnp.float32) * dt[..., None]).reshape(n, Q, H, P)
    cs = cs.reshape(n, Q, H)
    Bc = B.astype(jnp.float32).reshape(n, Q, G, N)
    Cc = C.astype(jnp.float32).reshape(n, Q, G, N)
    cb = jnp.einsum("cqgn,csgn->cgqs", Cc, Bc, precision=_HI)
    seg = cs[:, :, None, :] - cs[:, None, :, :]            # (n, q, s, H)
    lower = jnp.tril(jnp.ones((Q, Q), bool))[None, :, :, None]
    decay = jnp.where(lower, jnp.exp(jnp.minimum(seg, 0.0)), 0.0)
    scores = jnp.repeat(cb, H // G, axis=1).transpose(0, 2, 3, 1) * decay
    y = jnp.einsum("cqsh,cshp->cqhp", scores, xdt, precision=_HI)
    to_end = jnp.exp(cs[:, -1:, :] - cs)                    # (n, Q, H)
    Bh, Ch = _per_head(Bc, H), _per_head(Cc, H)
    grown = jnp.einsum("cqhp,cqhn->chpn", xdt * to_end[..., None], Bh,
                       precision=_HI)

    def carry(h, chunk_):
        total, add = chunk_
        return jnp.exp(total)[:, None, None] * h + add, h

    h, before = jax.lax.scan(carry, jnp.zeros((H, P, N), jnp.float32),
                             (cs[:, -1, :], grown))
    y = y + jnp.einsum("cqhn,chpn->cqhp", Ch, before, precision=_HI) \
        * jnp.exp(cs)[..., None]
    return y.reshape(T, H, P), h


def _chunk_scan_kernel(len_ref, keep_ref, x_ref, dt_ref, cs_ref, row_ref,
                       b_ref, c_ref, y_ref, h_ref, *, chunk, per_group):
    """One chunk of every head. ``x_ref`` ``(H, Q, P)``; ``dt_ref``,
    ``cs_ref`` ``(H, Q, 1)`` columns and ``row_ref`` ``(H, 1, Q)`` the same
    sums as rows (the decay matrix needs both); ``b_ref``, ``c_ref`` ``(G,
    Q, N)``; ``h_ref`` ``(H, P, N)`` is the state, resident over the grid."""
    c = pl.program_id(0)
    Q, heads = chunk, x_ref.shape[0]

    @pl.when(c == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(c * Q >= len_ref[0])
    def _():                       # a chunk of padding advances nothing
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(c * Q < len_ref[0])
    def _():
        lower = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0) \
            >= jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
        for g in range(b_ref.shape[0]):
            bg = b_ref[g]
            cg32 = c_ref[g].astype(jnp.float32)
            cb = jax.lax.dot_general(c_ref[g], bg, _NT,
                                     preferred_element_type=jnp.float32)
            bg32 = bg.astype(jnp.float32)

            def head(i, _, g=g, cb=cb, bg32=bg32, cg32=cg32):
                h = g * per_group + i
                col = cs_ref[h]                                   # (Q, 1)
                row = row_ref[h]                                  # (1, Q)
                decay = jnp.where(
                    lower, jnp.exp(jnp.minimum(col - row, 0.0)), 0.0)
                xdt = x_ref[h].astype(jnp.float32) * dt_ref[h]    # (Q, P)
                before = h_ref[h]                                 # (P, N)
                y = jnp.dot(cb * decay, xdt, precision=_HI,
                            preferred_element_type=jnp.float32)
                y = y + jnp.exp(col) * jax.lax.dot_general(
                    cg32, before, _NT, precision=_HI,
                    preferred_element_type=jnp.float32)
                y_ref[h] = y.astype(y_ref.dtype)
                total = col[Q - 1:Q, :]                           # (1, 1)
                grown = jax.lax.dot_general(
                    xdt * jnp.exp(total - col), bg32, _TN, precision=_HI,
                    preferred_element_type=jnp.float32)
                h_ref[h] = keep_ref[c * heads + h] * before + grown
                return 0

            jax.lax.fori_loop(0, per_group, head, 0)


def _chunk_scan_pallas(x, dt, cs, B, C, chunk, length):
    T, H, P = x.shape
    G, N = B.shape[1:]
    Q = chunk
    col = lambda a: a.T[:, :, None]                        # (H, T, 1)
    whole = lambda c, n, k: (0, c, 0)
    # what a chunk keeps of the state before it, a head: a scalar a (chunk,
    # head), read from SMEM (a (1, 1) vector cannot be broadcast both ways)
    keep = jnp.exp(cs.reshape(T // Q, Q, H)[:, -1, :]).reshape(-1)
    y, h = pl.pallas_call(
        lambda *refs: _chunk_scan_kernel(*refs, chunk=Q, per_group=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(T // Q,),
            in_specs=[pl.BlockSpec((H, Q, P), whole),
                      pl.BlockSpec((H, Q, 1), whole),
                      pl.BlockSpec((H, Q, 1), whole),
                      pl.BlockSpec((H, 1, Q), lambda c, n, k: (0, 0, c)),
                      pl.BlockSpec((G, Q, N), whole),
                      pl.BlockSpec((G, Q, N), whole)],
            out_specs=[pl.BlockSpec((H, Q, P), whole),
                       pl.BlockSpec((H, P, N), lambda c, n, k: (0, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct((H, T, P), jnp.float32),
                   jax.ShapeDtypeStruct((H, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interp(),
        name="mamba2_chunk_scan",
    )(jnp.reshape(length, (1,)).astype(jnp.int32), keep,
      x.transpose(1, 0, 2),
      col(dt), col(cs), cs.T[:, None, :], B.transpose(1, 0, 2),
      C.transpose(1, 0, 2))
    return y.transpose(1, 0, 2), h


def mamba2_chunk_scan(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                      B: jnp.ndarray, C: jnp.ndarray, *, chunk: int,
                      length=None, use_pallas: Optional[bool] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The scan of one sequence in chunks of ``chunk`` tokens (module
    docstring): ``x`` ``(T, H, P)``; ``dt`` ``(T, H)`` float32, after its
    softplus; ``A`` ``(H,)`` float32, negative; ``B``, ``C`` ``(T, G, N)``,
    head ``i`` in group ``i // (H / G)``; ``length`` (int32 scalar, traced)
    the real tokens, ``T`` when None. Returns ``(y (T, H, P) float32, the
    state (H, P, N) float32 after token length - 1)``. A ``T`` that is no
    whole chunks is padded to them; the ``D x`` skip, the gate and the norm
    are the caller's."""
    T = x.shape[0]
    length = jnp.asarray(T if length is None else length, jnp.int32)
    if T % chunk:              # a last chunk of padding, which adds nothing
        pad = lambda a: jnp.pad(a, [(0, -T % chunk)] + [(0, 0)] * (a.ndim - 1))
        y, h = mamba2_chunk_scan(pad(x), pad(dt), A, pad(B), pad(C),
                                 chunk=chunk, length=length,
                                 use_pallas=use_pallas)
        return y[:T], h
    dt = jnp.where((jnp.arange(T) < length)[:, None],
                   dt.astype(jnp.float32), 0.0)
    A = A.astype(jnp.float32)
    fits = supports_chunk_scan(T, chunk, B.shape[-1])
    if use_pallas is None:
        use_pallas = fits
    elif use_pallas and not fits and not _interp():
        raise ValueError(
            f"mamba2_chunk_scan: chunks of {chunk} tokens over a state of "
            f"{B.shape[-1]} do not sit on the (8, 128) tiles")
    cs = _chunk_sums(dt, A, chunk)
    with jax.named_scope("mamba2_scan"):
        if use_pallas:
            return _chunk_scan_pallas(x, dt, cs, B, C, chunk, length)
        return _chunk_scan_xla(x, dt, cs, B, C, chunk)

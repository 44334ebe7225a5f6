"""Mamba-1 (selective scan) pieces for serving: the scan of a prefill and the
one-token update of a decode step (each a Pallas kernel, an XLA form and,
for the scan, a token-by-token oracle). The causal conv in front of both is
:func:`apex_tpu.ops.mamba2.causal_conv` / ``causal_conv_update`` as they are.

The recurrence of one layer (``E`` channels, ``N`` state indices, float32):

    h_t[n, c] = exp(delta_t[c] A[n, c]) h_{t-1}[n, c] + delta_t[c] B_t[n] u_t[c]
    y_t[c]    = sum_n C_t[n] h_t[n, c]

``A < 0`` a (state index, channel), ``delta_t > 0`` a (token, channel),
``B_t``, ``C_t`` ``(N,)`` shared by every channel. Unlike Mamba-2
(:mod:`apex_tpu.ops.mamba2`: ONE decay a head, so a chunk is a decay-masked
``(Q, Q)`` product on the MXU) the decay differs for every one of the ``N *
E`` state entries and there is no matrix form: the work is elementwise, ``N *
E`` multiply-adds and exponentials a token, on the VPU and EUP.

Everything here is LANE-MAJOR: the state is ``(N, E)``, channels last, and
``A`` comes the same way. At ``N = 16`` a ``(..., E, N)`` array would fill 16
of a tile's 128 lanes.

Three forms of the scan:

- :func:`mamba1_recurrence`, the oracle: one ``lax.scan`` step a token;
- a Pallas kernel named ``mamba1_selective_scan``: grid ``(channel blocks,
  time chunks)``, the time axis in order, the state of a channel block
  resident in VMEM between chunks. A channel block is 1,024 channels laid
  over a whole ``(8, 128)`` tile (channels across lanes AND sublanes), so the
  state of one index ``n`` is one full vreg, ``B_t[n]`` and ``C_t[n]`` are
  scalars read from SMEM, and a token costs ``N`` times (one exponential,
  ~six VALU operations) on full vregs;
- the same in XLA ops (a chunk's ``(Q, N, E)`` decays and inputs folded by
  an associative scan, the chunks in a ``lax.scan``), behind the kernels'
  usual gate (``use_pallas=None``: the kernel where the shapes sit on the
  tiles).

Positions at or past ``length`` are padding: their ``delta`` is taken as 0,
so they decay nothing and add nothing and the state that comes back is the
last REAL token's; the kernel runs a chunk's real tokens only and skips a
chunk that is all padding.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.backend import pallas_interpret as _interp

__all__ = ["mamba1_selective_scan", "mamba1_recurrence",
           "mamba1_decode_update", "supports_selective_scan",
           "supports_decode_update"]

_SUB, _LANES = 8, 128
_BLOCK = _SUB * _LANES        # channels a kernel block: one whole tile
_UNROLL = 8                   # tokens a trip of the kernel's loop
_LOG2E = 1.4426950408889634


def supports_selective_scan(tokens: int, chunk: int, channels: int) -> bool:
    """Whether the kernel's blocks sit on the (8, 128) tiles: whole chunks
    of a multiple of 8 tokens, channels in whole 1,024-channel blocks."""
    return tokens % chunk == 0 and chunk % _UNROLL == 0 \
        and channels % _BLOCK == 0


# -- token by token -----------------------------------------------------------


def mamba1_recurrence(u, delta, A, B, C, length=None):
    """The recurrence as written, one token a step: the oracle of the other
    forms. Shapes as :func:`mamba1_selective_scan`; float32."""
    T = u.shape[0]
    delta = delta.astype(jnp.float32)
    if length is not None:
        delta = jnp.where((jnp.arange(T) < length)[:, None], delta, 0.0)
    A = A.astype(jnp.float32)

    def step(h, row):
        u_t, d_t, b_t, c_t = row
        h = jnp.exp(d_t[None, :] * A) * h \
            + (d_t * u_t)[None, :] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=0)

    h, y = jax.lax.scan(step, jnp.zeros(A.shape, jnp.float32),
                        (u.astype(jnp.float32), delta,
                         B.astype(jnp.float32), C.astype(jnp.float32)))
    return y, h


def supports_decode_update(slots: int, channels: int) -> bool:
    """Whether the decode kernel's blocks sit on the (8, 128) tiles: slots
    in whole blocks of 8, channels in whole lanes."""
    return slots % _SUB == 0 and channels % _LANES == 0


def _decode_kernel(layer_ref, valid_ref, dt_ref, du_ref, b_ref, c_ref, a_ref,
                   h_ref, y_ref, out_ref):
    """Eight slots of one channel block at one layer: ``h_ref``, ``out_ref``
    ``(1, 8, N, CB)`` the SAME rows of the state (aliased in and out);
    ``dt_ref``, ``du_ref``, ``y_ref`` ``(8, CB)``; ``b_ref``, ``c_ref`` ``(8,
    N, 1)`` columns; ``a_ref`` ``(N, CB)`` holding ``A log2(e)``."""
    del layer_ref                               # the index maps' business
    first = pl.program_id(0) * _SUB
    a = a_ref[...]
    for s in range(_SUB):
        old = h_ref[0, s]
        row = pl.ds(s, 1)
        new = jnp.exp2(dt_ref[row, :] * a) * old + du_ref[row, :] * b_ref[s]
        y_ref[row, :] = jnp.sum(new * c_ref[s], axis=0, keepdims=True)
        out_ref[0, s] = jnp.where(valid_ref[first + s] != 0, new, old)


def _decode_pallas(states, layer, du, delta, A, B, C, valid):
    L, S, N, E = states.shape
    block = max(c for c in range(_LANES, min(E, 2560) + 1, _LANES)
                if E % c == 0)
    rows = pl.BlockSpec((_SUB, block), lambda i, j, lay, ok: (i, j))
    cols = pl.BlockSpec((_SUB, N, 1), lambda i, j, lay, ok: (i, 0, 0))
    held = pl.BlockSpec((1, _SUB, N, block),
                        lambda i, j, lay, ok: (lay[0], i, 0, j))
    y, states = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(S // _SUB, E // block),
            in_specs=[rows, rows, cols, cols,
                      pl.BlockSpec((N, block), lambda i, j, lay, ok: (0, j)),
                      held],
            out_specs=[rows, held]),
        out_shape=[jax.ShapeDtypeStruct((S, E), jnp.float32),
                   jax.ShapeDtypeStruct(states.shape, jnp.float32)],
        # the state (operand 7, the two prefetched scalars counted) IS the
        # second result: rows are updated where they lie
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interp(),
        name="mamba1_decode_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), valid.astype(jnp.int32),
      delta, du, B[:, :, None], C[:, :, None], A * _LOG2E, states)
    return y, states


def mamba1_decode_update(states, layer, u, delta, A, B, C, valid=None,
                         use_pallas: Optional[bool] = None):
    """One token a slot at one layer of the stacked state: ``states`` ``(L,
    S, N, E)`` float32 (donated by the caller's program: the result takes its
    place), ``layer`` an int32 scalar, ``u``, ``delta`` ``(S, E)``, ``A``
    ``(N, E)``, ``B``, ``C`` ``(S, N)``. Returns ``(y (S, E) float32, the
    states with layer ``layer`` moved on)``; a slot outside ``valid`` keeps
    its rows as they were. The kernel ``mamba1_decode_update`` reads a row
    once and writes it once where it lies; the XLA form is two fusions (the
    read-out reads the layer's rows, the in-place update reads and writes
    them)."""
    S, E = u.shape
    fits = supports_decode_update(S, E)
    if use_pallas is None:
        use_pallas = fits
    elif use_pallas and not fits:
        raise ValueError(f"mamba1_decode_update: {S} slots of {E} channels "
                         "do not sit on the (8, 128) tiles")
    delta = delta.astype(jnp.float32)
    du = delta * u.astype(jnp.float32)
    A, B, C = (x.astype(jnp.float32) for x in (A, B, C))
    if valid is None:
        valid = jnp.ones((S,), jnp.bool_)
    with jax.named_scope("mamba1_decode_update"):
        if use_pallas:
            return _decode_pallas(states, layer, du, delta, A, B, C, valid)
        old = states[layer]
        new = jnp.exp(delta[:, None, :] * A[None]) * old \
            + du[:, None, :] * B[:, :, None]
        y = jnp.sum(new * C[:, :, None], axis=1)
        new = jnp.where(valid[:, None, None], new, old)
        return y, states.at[layer].set(new)


# -- chunked, XLA -------------------------------------------------------------


def _scan_xla(du, delta, A, B, C, chunk):
    """``h_t = a_t h_{t-1} + b_t`` folded by an associative scan inside a
    chunk (``(Q, N, E)`` at a time), the chunks in order."""
    T, E = du.shape
    N = A.shape[0]
    n = T // chunk
    rows = lambda x: x.reshape((n, chunk) + x.shape[1:])

    def fold(left, right):
        (a1, b1), (a2, b2) = left, right
        return a1 * a2, a2 * b1 + b2

    def one(h, row):
        du_c, d_c, b_c, c_c = row
        decay = jnp.exp(d_c[:, None, :] * A[None])           # (Q, N, E)
        grown = du_c[:, None, :] * b_c[:, :, None]
        kept, own = jax.lax.associative_scan(fold, (decay, grown), axis=0)
        states = kept * h[None] + own
        return states[-1], jnp.sum(states * c_c[:, :, None], axis=1)

    h, y = jax.lax.scan(one, jnp.zeros((N, E), jnp.float32),
                        (rows(du), rows(delta), rows(B), rows(C)))
    return y.reshape(T, E), h


# -- chunked, the kernel ------------------------------------------------------


def _scan_kernel(len_ref, b_ref, c_ref, du_ref, dt_ref, a_ref, y_ref, h_ref,
                 *, chunk, states):
    """One chunk of one channel block. ``du_ref``, ``dt_ref``, ``y_ref``
    ``(Q, 8, 128)``: a token's 1,024 channels are one tile; ``a_ref``,
    ``h_ref`` ``(N, 8, 128)``, the state resident over the chunks, ``a_ref``
    holding ``A log2(e)`` (the EUP raises 2 to a power: the scaling costs
    a multiply a state entry a token here, nothing done once outside);
    ``b_ref``, ``c_ref`` ``(Q * N,)`` in SMEM, token-major."""
    c = pl.program_id(1)
    Q, N = chunk, states
    live = jnp.clip(len_ref[0] - c * Q, 0, Q)

    @pl.when(c == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(live < Q)
    def _():                       # the rows past the prompt's end
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(live > 0)
    def _():
        def token(t, h):
            d = dt_ref[t]
            du = du_ref[t]
            new, parts = [], []
            for n in range(N):
                hn = jnp.exp2(d * a_ref[n]) * h[n] + du * b_ref[t * N + n]
                new.append(hn)
                parts.append(hn * c_ref[t * N + n])
            while len(parts) > 1:          # a tree, not a chain of N adds
                parts = [parts[i] + parts[i + 1]
                         for i in range(0, len(parts), 2)]
            y_ref[t] = parts[0]
            return tuple(new)

        def trip(i, h):
            for k in range(_UNROLL):
                h = token(i * _UNROLL + k, h)
            return h

        # whole trips over the real tokens: the rows a last trip runs past
        # them have delta 0 and move nothing
        h = jax.lax.fori_loop(0, (live + _UNROLL - 1) // _UNROLL, trip,
                              tuple(h_ref[n] for n in range(N)))
        for n in range(N):
            h_ref[n] = h[n]


def _scan_pallas(du, delta, A, B, C, chunk, length):
    T, E = du.shape
    N = A.shape[0]
    Q = chunk
    tiles = lambda x: x.reshape(x.shape[0], E // _LANES, _LANES)
    block = lambda c_blk, c, n: (c, c_blk, 0)
    whole = lambda c_blk, c, n: (0, c_blk, 0)
    scalars = pl.BlockSpec((Q * N,), lambda c_blk, c, n: (c,),
                           memory_space=pltpu.SMEM)
    y, h = pl.pallas_call(
        lambda *refs: _scan_kernel(*refs, chunk=Q, states=N),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(E // _BLOCK, T // Q),
            in_specs=[scalars, scalars,
                      pl.BlockSpec((Q, _SUB, _LANES), block),
                      pl.BlockSpec((Q, _SUB, _LANES), block),
                      pl.BlockSpec((N, _SUB, _LANES), whole)],
            out_specs=[pl.BlockSpec((Q, _SUB, _LANES), block),
                       pl.BlockSpec((N, _SUB, _LANES), whole)]),
        out_shape=[jax.ShapeDtypeStruct((T, E // _LANES, _LANES),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((N, E // _LANES, _LANES),
                                        jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=_interp(),
        name="mamba1_selective_scan",
    )(jnp.reshape(length, (1,)).astype(jnp.int32), B.reshape(-1),
      C.reshape(-1), tiles(du), tiles(delta), tiles(A * _LOG2E))
    return y.reshape(T, E), h.reshape(N, E)


def mamba1_selective_scan(u: jnp.ndarray, delta: jnp.ndarray, A: jnp.ndarray,
                          B: jnp.ndarray, C: jnp.ndarray, *, chunk: int,
                          length=None, use_pallas: Optional[bool] = None
                          ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The scan of one sequence from a zero state (module docstring): ``u``
    ``(T, E)``, the conv's output; ``delta`` ``(T, E)`` float32, after its
    softplus; ``A`` ``(N, E)`` float32, negative; ``B``, ``C`` ``(T, N)``;
    ``length`` (int32 scalar, traced) the real tokens, ``T`` when None.
    Returns ``(y (T, E) float32, the state (N, E) float32 after token
    length - 1)``. A ``T`` that is no whole chunks is padded to them; the
    ``D u`` skip and the gate are the caller's."""
    T, E = u.shape
    length = jnp.asarray(T if length is None else length, jnp.int32)
    if T % chunk:              # a last chunk of padding, which adds nothing
        pad = lambda a: jnp.pad(a, [(0, -T % chunk), (0, 0)])
        y, h = mamba1_selective_scan(pad(u), pad(delta), A, pad(B), pad(C),
                                     chunk=chunk, length=length,
                                     use_pallas=use_pallas)
        return y[:T], h
    fits = supports_selective_scan(T, chunk, E)
    if use_pallas is None:
        use_pallas = fits
    elif use_pallas and not fits:
        raise ValueError(
            f"mamba1_selective_scan: chunks of {chunk} tokens over {E} "
            f"channels do not sit on the (8, 128) tiles")
    delta = jnp.where((jnp.arange(T) < length)[:, None],
                      delta.astype(jnp.float32), 0.0)
    du = delta * u.astype(jnp.float32)
    A, B, C = (x.astype(jnp.float32) for x in (A, B, C))
    with jax.named_scope("mamba1_scan"):
        if use_pallas:
            return _scan_pallas(du, delta, A, B, C, chunk, length)
        return _scan_xla(du, delta, A, B, C, chunk)

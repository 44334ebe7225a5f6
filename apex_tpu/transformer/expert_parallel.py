"""Expert parallelism (MoE) — EP over a mesh axis.

The reference has **no** MoE (SURVEY §2.3: "EP — not required for parity;
note for roadmap"); on TPU expert parallelism is a first-class mesh axis,
so the roadmap item ships: a Switch-style top-1 routed MLP whose experts
shard over a mesh axis, with the canonical GShard dispatch/combine
einsums and one ``all_to_all`` each way (the collective EP exists for —
tokens travel to their expert's device and back over ICI).

Design (single SPMD program, static shapes):

1. router: ``gates = softmax(x @ Wg)``; top-1 expert per token, with the
   Switch load-balancing auxiliary loss;
2. capacity ``C = ceil(tokens_local * capacity_factor / E)``; per-expert
   positions via cumsum; tokens beyond capacity are dropped (their output
   is 0 and the residual path carries them, as in Switch);
3. dispatch einsum builds ``(E, C, d)`` slots; ``all_to_all`` re-shards
   from token-sharded to expert-sharded: each device receives the slots
   bound for ITS local experts from every peer;
4. local expert FFNs (dense -> gelu -> dense), vmapped over local experts;
5. reverse ``all_to_all``; combine einsum scatters expert outputs back to
   token positions, scaled by the gate.

Beside it, for SERVING a sparse model one chip holds a share of:
:class:`HeldExpertsMLP` (section comment further down) — dropless top-k
sigmoid routing over the published width, SwiGLU experts, shared experts
averaged, the chip told which experts it holds; its work follows the
assignments through a grouped product of two Pallas kernels.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.utils.backend import pallas_interpret as _interp

from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from apex_tpu.transformer.tensor_parallel.layers import init_method_normal
from jax.lax import axis_size as _axis_size

__all__ = ["ExpertParallelMLP", "HeldExpertsMLP", "grouped_swiglu"]


class ExpertParallelMLP:
    """Switch-style top-1 MoE MLP with experts sharded over ``axis_name``.

    ``num_experts`` must divide by the axis size; parameters come back from
    :meth:`init` stacked ``(num_experts, ...)`` — shard axis 0 over the
    expert axis (``P(axis_name)``); the router is replicated.

    ``__call__(params, x)`` with ``x`` ``(tokens_local, hidden)`` (flatten
    batch x seq first) returns ``(out, aux_loss)`` — ``aux_loss`` is the
    Switch load-balancing term (mean over devices is up to the caller).
    """

    def __init__(self, hidden_size: int, ffn_hidden_size: int,
                 num_experts: int, capacity_factor: float = 1.25,
                 axis_name: str = TENSOR_AXIS,
                 init_method=None, params_dtype=jnp.float32):
        self.hidden_size = hidden_size
        self.ffn = ffn_hidden_size
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.axis_name = axis_name
        self.init_method = init_method or init_method_normal(0.02)
        self.params_dtype = params_dtype

    def init(self, key: jax.Array) -> dict:
        E, h, f = self.num_experts, self.hidden_size, self.ffn
        kr, k1, k2 = jax.random.split(key, 3)
        return {
            "router": {"weight": self.init_method(kr, (E, h)).astype(
                self.params_dtype)},
            "experts": {
                "wi": self.init_method(k1, (E, f, h)).astype(
                    self.params_dtype),
                "bi": jnp.zeros((E, f), self.params_dtype),
                "wo": self.init_method(k2, (E, h, f)).astype(
                    self.params_dtype),
                "bo": jnp.zeros((E, h), self.params_dtype),
            },
        }

    # -- pieces -----------------------------------------------------------
    def _route(self, params, x):
        """Top-1 gates + dispatch/combine tensors (GShard einsum form)."""
        E = self.num_experts
        n = x.shape[0]
        C = max(1, math.ceil(n * self.capacity_factor / E))
        logits = (x.astype(jnp.float32)
                  @ params["router"]["weight"].astype(jnp.float32).T)
        gates = jax.nn.softmax(logits, axis=-1)           # (n, E)
        expert = jnp.argmax(gates, axis=-1)               # (n,)
        gate = jnp.max(gates, axis=-1)                    # (n,)
        onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)
        # position of each token within its expert's queue
        pos = jnp.cumsum(onehot, axis=0) * onehot         # 1-based
        pos = jnp.sum(pos, axis=-1) - 1.0                 # (n,), -1 if none
        keep = pos < C
        gate = gate * keep
        # dispatch (n, E, C): token -> expert slot
        pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C,
                                dtype=jnp.float32)        # (n, C)
        dispatch = onehot[:, :, None] * pos_oh[:, None, :] \
            * keep[:, None, None]
        combine = dispatch * gate[:, None, None]
        # Switch aux loss: E * sum_e fraction_e * mean_prob_e
        frac = jnp.mean(onehot, axis=0)
        prob = jnp.mean(gates, axis=0)
        aux = E * jnp.sum(frac * prob)
        return dispatch, combine, aux, C

    def _expert_ffn(self, ep_params, slots):
        """slots: (E_local, S, h) -> (E_local, S, h), vmapped experts."""
        def one(wi, bi, wo, bo, xs):
            dt = xs.dtype
            h1 = jax.nn.gelu(xs @ wi.astype(dt).T + bi.astype(dt),
                             approximate=True)
            return h1 @ wo.astype(dt).T + bo.astype(dt)
        return jax.vmap(one)(ep_params["wi"], ep_params["bi"],
                             ep_params["wo"], ep_params["bo"], slots)

    # -- forward ----------------------------------------------------------
    def __call__(self, params: dict, x: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        E = self.num_experts
        ep = _axis_size(self.axis_name)
        if E % ep:
            raise ValueError(f"num_experts {E} not divisible by ep={ep}")
        e_loc = E // ep
        dispatch, combine, aux, C = self._route(params, x)

        dt = x.dtype
        # (n, E, C) x (n, h) -> (E, C, h) slots on the source device
        slots = jnp.einsum("nec,nh->ech", dispatch.astype(jnp.float32),
                           x.astype(jnp.float32)).astype(dt)
        # token-sharded -> expert-sharded: split the E axis, gather peers'
        # slots for my local experts along the capacity axis
        slots = jax.lax.all_to_all(slots, self.axis_name, split_axis=0,
                                   concat_axis=1, tiled=True)
        # (e_loc, ep*C, h) through the local experts
        out_slots = self._expert_ffn(params["experts"], slots)
        out_slots = jax.lax.all_to_all(out_slots, self.axis_name,
                                       split_axis=1, concat_axis=0,
                                       tiled=True)
        # combine back to token positions, gate-scaled
        out = jnp.einsum("nec,ech->nh", combine.astype(jnp.float32),
                         out_slots.astype(jnp.float32))
        return out.astype(dt), aux


# ---------------------------------------------------------------------------
# dropless top-k experts, the share one chip holds
# ---------------------------------------------------------------------------
#
# The serving-side layer of a sparse model whose experts are divided over
# the chips that share a layer while every chip sees every token (attention
# is divided by heads there, so the tokens are not): each chip routes every
# token over ALL the published experts, keeps the picks that name an expert
# it holds, and adds its part; the parts of the chips sum to the layer.
# Nothing is dropped and no capacity is set, so the work is the
# assignments': rows are sorted by expert into tiles of ``tile_m`` rows,
# each tile one expert's, and two Pallas kernels (``moe_experts_gate_up``,
# ``moe_experts_down``) multiply the used tiles with their expert's
# matrices. The grid is shaped by the worst case (every pick held), but a
# tile past the used ones costs a grid step and nothing else: its index maps
# resolve to the last used tile (no fetch) and its compute is skipped. The
# weights ride as ONE stacked array a layer kind with the layer's index as
# a scalar-prefetch argument, so the layer scan slices nothing: an expert's
# matrices are read where they lie, and only those of experts with a row.

_VMEM_LIMIT = 64 * 1024 * 1024


def _tile_n(n: int) -> int:
    for t in (512, 256, 128):
        if n % t == 0:
            return t
    return n


def _grouped_specs(tile_m, k, tn):
    def tile(m, used):
        # a tile past the used ones resolves to the last used one
        return jnp.minimum(m, jnp.maximum(used[0] - 1, 0))

    def rows(n, m, lay, tile_e, used):
        return (tile(m, used), 0)

    def weight(n, m, lay, tile_e, used):
        return (lay[0], tile_e[tile(m, used)], 0, n)

    def out(n, m, lay, tile_e, used):
        return (tile(m, used), n)

    return (pl.BlockSpec((tile_m, k), rows),
            pl.BlockSpec((1, 1, k, tn), weight),
            pl.BlockSpec((tile_m, tn), out))


def _gate_up_kernel(lay_ref, tile_ref, used_ref, x_ref, wg_ref, wu_ref,
                    o_ref):
    del lay_ref, tile_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0, 0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0, 0], preferred_element_type=jnp.float32)
        o_ref[...] = (jax.nn.silu(g) * u).astype(o_ref.dtype)


def _down_kernel(lay_ref, tile_ref, used_ref, x_ref, w_ref, o_ref):
    del lay_ref, tile_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0, 0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def _grouped_call(kernel, name, xs, weights, layer, tile_expert, used,
                  tile_m):
    M, K = xs.shape
    N = weights[0].shape[-1]
    tn = _tile_n(N)
    x_spec, w_spec, o_spec = _grouped_specs(tile_m, K, tn)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(N // tn, M // tile_m),
            in_specs=[x_spec] + [w_spec] * len(weights),
            out_specs=o_spec),
        out_shape=jax.ShapeDtypeStruct((M, N), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interp(),
        name=name,
    )(jnp.reshape(layer, (1,)), tile_expert, jnp.reshape(used, (1,)), xs,
      *weights)


def grouped_swiglu(xs, w_gate, w_up, w_down, layer, tile_expert, used,
                   tile_m: int):
    """``(silu(xs Wg[e]) * (xs Wu[e])) Wd[e]`` for rows sorted into tiles
    of ``tile_m``, tile ``t`` multiplied with expert ``tile_expert[t]`` of
    layer ``layer`` of the stacked ``(layers, experts, in, out)`` weights;
    only the first ``used`` tiles are computed or fetched."""
    with jax.named_scope("moe_experts"):
        mid = _grouped_call(_gate_up_kernel, "moe_experts_gate_up", xs,
                            (w_gate, w_up), layer, tile_expert, used, tile_m)
        return _grouped_call(_down_kernel, "moe_experts_down", mid,
                             (w_down,), layer, tile_expert, used, tile_m)


class HeldExpertsMLP:
    """Dropless top-``top_k`` sigmoid-routed SwiGLU experts, the part of
    one chip that holds the experts ``held`` (ids in the published
    numbering) of ``num_experts``, beside ``num_shared`` shared experts
    whose mean is added for every token (section comment above).

    The router keeps its published width: a token's weights are its
    ``top_k`` largest sigmoid scores over ALL experts, normalised over all
    ``top_k`` picks; the picks that are held add ``w_e E_e(x)``, the
    others nothing. Parameters (``init``): ``router`` ``(hidden,
    num_experts)`` and ``w_gate``/``w_up`` ``(n, hidden, expert_size)``,
    ``w_down`` ``(n, expert_size, hidden)`` with ``n = len(held) +
    num_shared``, the held experts in the order of ``held``, then the
    shared ones.

    ``__call__(params, x, valid=None, layer=None)``: ``x`` ``(tokens,
    hidden)``; ``valid`` ``(tokens,)`` bool masks padding and idle slots
    out of the routing (no row, no count); with ``layer`` the three expert
    arrays carry a leading layer axis and ``layer`` (int32 scalar, traced
    in the layer scan) says which to read — the router is that layer's
    own; ``held`` as in :meth:`route`. Returns ``(out (tokens, hidden), {"load": (len(held),) int32
    assignments by held expert, "no_held_pick": int32 valid tokens none of
    whose picks is held})``.

    ``axis_name``: inside ``shard_map`` over the chips that share the
    layer, each passing its own ``held`` weights, the parts are summed
    over the axis (the layer's one exchange, an all-reduce, since every
    chip holds every token) and the shared experts counted once. On one
    chip there is no exchange."""

    def __init__(self, hidden_size: int, expert_size: int, num_experts: int,
                 top_k: int, held: Sequence[int], num_shared: int = 0,
                 axis_name: Optional[str] = None,
                 params_dtype=jnp.bfloat16, init_std: float = 0.02,
                 use_pallas: bool = True):
        held = tuple(int(e) for e in held)
        if len(set(held)) != len(held) or not held \
                or not all(0 <= e < num_experts for e in held):
            raise ValueError(f"held experts {held} are not distinct ids "
                             f"below {num_experts}")
        if not 0 < top_k <= num_experts:
            raise ValueError(f"top_k {top_k} outside (0, {num_experts}]")
        self.hidden_size, self.expert_size = hidden_size, expert_size
        self.num_experts, self.top_k = num_experts, top_k
        self.held, self.num_shared = held, int(num_shared)
        self.axis_name = axis_name
        self.params_dtype = params_dtype
        self.init_std = init_std
        self.use_pallas = use_pallas

    @property
    def num_local(self) -> int:
        return len(self.held) + self.num_shared

    def init(self, key: jax.Array) -> dict:
        n, h, f = self.num_local, self.hidden_size, self.expert_size
        kr, kg, ku, kd = jax.random.split(key, 4)
        draw = lambda k, shape: (self.init_std * jax.random.normal(
            k, shape, jnp.float32)).astype(self.params_dtype)
        return {"router": draw(kr, (h, self.num_experts)),
                "w_gate": draw(kg, (n, h, f)), "w_up": draw(ku, (n, h, f)),
                "w_down": draw(kd, (n, f, h))}

    def tile_m(self, tokens: int) -> int:
        """Rows a tile: about the rows an expert gets when the picks are
        spread evenly, within [32, 256] (a tile is one expert's, so its
        last one is padded: small tiles where the rows are few)."""
        per = max(1, tokens * self.top_k // self.num_experts)
        return int(min(256, max(32, 1 << (per - 1).bit_length())))

    def route(self, router, x, valid=None, held=None):
        """``(local (tokens, picks) int32, weight (tokens, picks) f32)``:
        per token its routed picks then the shared experts, ``local`` the
        index into this chip's expert stack or -1 (not held, or the token
        is not ``valid``). ``held``: the ids as a traced ``(len(held),)``
        array in the place of the constructor's (a chip that learns its
        experts from its place on the axis)."""
        T = x.shape[0]
        ids = np.asarray(self.held, np.int32) if held is None else held
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        top_s, top_e = jax.lax.top_k(scores, self.top_k)
        weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
        match = top_e[..., None] == ids[None, None, :]
        local = jnp.where(jnp.any(match, axis=-1),
                          jnp.argmax(match, axis=-1), -1).astype(jnp.int32)
        if self.num_shared:
            ns = self.num_shared
            share = 1.0 / ns
            if self.axis_name is not None:
                share /= jax.lax.axis_size(self.axis_name)
            local = jnp.concatenate([local, jnp.broadcast_to(
                len(self.held) + jnp.arange(ns, dtype=jnp.int32),
                (T, ns))], axis=1)
            weight = jnp.concatenate(
                [weight, jnp.full((T, ns), share, jnp.float32)], axis=1)
        if valid is not None:
            local = jnp.where(valid[:, None], local, -1)
        return local, weight

    def __call__(self, params: dict, x: jnp.ndarray,
                 valid: Optional[jnp.ndarray] = None, layer=None,
                 held=None):
        T = x.shape[0]
        nh = len(self.held)
        local, weight = self.route(params["router"], x, valid, held)
        routed = local[:, :self.top_k]
        onehot_r = (routed[..., None] == jnp.arange(nh)).astype(jnp.int32)
        ok = jnp.ones((T,), bool) if valid is None else valid
        stats = {"load": jnp.sum(onehot_r, axis=(0, 1)),
                 "no_held_pick": jnp.sum(
                     ok & ~jnp.any(routed >= 0, axis=1)).astype(jnp.int32)}
        w_gate, w_up, w_down = (params[k] for k in
                                ("w_gate", "w_up", "w_down"))
        if layer is None:
            w_gate, w_up, w_down = w_gate[None], w_up[None], w_down[None]
            layer = 0
        layer = jnp.asarray(layer, jnp.int32)
        if self.use_pallas:
            out = self._sorted_product(x, local, weight, w_gate, w_up,
                                       w_down, layer)
        else:
            out = self._dense_product(x, local, weight, w_gate[layer],
                                      w_up[layer], w_down[layer])
        if self.axis_name is not None:
            out = jax.lax.psum(out, self.axis_name)
        return out.astype(x.dtype), stats

    def _dense_product(self, x, local, weight, w_gate, w_up, w_down):
        """Every expert over every token, masked: experts x tokens of
        work, the oracle of the sorted product."""
        n = self.num_local
        per = jnp.sum(jnp.where(
            local[..., None] == jnp.arange(n), weight[..., None], 0.0),
            axis=1)                                        # (T, n)
        x32 = x.astype(jnp.float32)
        hi = jax.lax.Precision.HIGHEST
        out = jnp.zeros(x.shape, jnp.float32)
        for e in range(n):
            mid = jax.nn.silu(jnp.dot(x32, w_gate[e].astype(jnp.float32),
                                      precision=hi)) \
                * jnp.dot(x32, w_up[e].astype(jnp.float32), precision=hi)
            out = out + per[:, e:e + 1] * jnp.dot(
                mid, w_down[e].astype(jnp.float32), precision=hi)
        return out

    def _sorted_product(self, x, local, weight, w_gate, w_up, w_down,
                        layer):
        T, H = x.shape
        n, picks = self.num_local, local.shape[1]
        tm = self.tile_m(T)
        A = T * picks
        # rows in the worst case (a token's routed picks are distinct, so
        # an expert gets at most T rows) and every expert's last tile
        rows = T * (min(self.top_k, len(self.held)) + self.num_shared)
        M = -(-rows // tm) * tm + n * tm
        flat = local.reshape(A)
        onehot = (flat[:, None] == jnp.arange(n)).astype(jnp.int32)
        counts = jnp.sum(onehot, axis=0)
        rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot,
                       axis=1)
        padded = -(-counts // tm) * tm
        ends = jnp.cumsum(padded)
        dest = jnp.where(flat >= 0,
                         (ends - padded)[jnp.maximum(flat, 0)] + rank, M)
        token_of_row = jnp.zeros((M,), jnp.int32).at[dest].set(
            jnp.arange(A, dtype=jnp.int32) // picks, mode="drop")
        tile_expert = jnp.minimum(jnp.searchsorted(
            ends, jnp.arange(M // tm, dtype=jnp.int32) * tm, side="right"),
            n - 1).astype(jnp.int32)
        used = (ends[-1] // tm).astype(jnp.int32)
        xs = jnp.take(x, token_of_row, axis=0)
        ys = grouped_swiglu(xs, w_gate, w_up, w_down, layer, tile_expert,
                            used, tm)
        # each token gathers its own picks' rows back: a gather, where a
        # scatter-add over the rows would serialise
        held = (flat >= 0).reshape(T, picks)
        got = jnp.take(ys, jnp.minimum(dest, M - 1).reshape(T, picks),
                       axis=0).astype(jnp.float32)
        return jnp.sum(jnp.where(held[..., None], got, 0.0)
                       * weight[..., None], axis=1)

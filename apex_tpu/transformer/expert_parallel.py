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
sigmoid routing over the published width, the chip told which experts it
holds, its work following the assignments through a grouped product of two
Pallas kernels. One class; the expert's form (gated SiLU or squared ReLU), a
selection bias, a scaling factor, a latent round the routed part and the
shared part (riding in the grouped product or an expert of its own width)
are its parameters.
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

__all__ = ["ExpertParallelMLP", "HeldExpertsMLP", "grouped_experts"]


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
# each tile one expert's, and two Pallas kernels (``moe_experts_gate_up``
# for a gated expert or ``moe_experts_up`` for one up matrix, then
# ``moe_experts_down``) multiply the used tiles with their expert's
# matrices. The grid is shaped by the worst case (every pick held), but a
# tile past the used ones costs a grid step and nothing else: its index maps
# resolve to the last used tile (no fetch) and its compute is skipped. The
# weights ride as ONE stacked array a layer kind with the layer's index as
# a scalar-prefetch argument, so the layer scan slices nothing: an expert's
# matrices are read where they lie, and only those of experts with a row.

_VMEM_LIMIT = 64 * 1024 * 1024
# the most one block of an expert's matrix may take: double-buffered, and
# two matrices in the gated kernel, it has to leave the limit room
_WEIGHT_BLOCK_BYTES = 8 * 1024 * 1024

ACTIVATIONS = ("swiglu", "relu2")


def _tile_n(n: int, k: int, itemsize: int = 2) -> int:
    """Columns of an expert's matrix a block: all of them where the whole
    ``(k, n)`` matrix is small (1024 x 2688 bf16 is one 5.5 MB fetch, not
    21 of 256 KB), else the widest of 512 / 256 / 128 that divides."""
    if k * n * itemsize <= _WEIGHT_BLOCK_BYTES:
        return n
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


def _up_kernel(lay_ref, tile_ref, used_ref, x_ref, w_ref, o_ref):
    del lay_ref, tile_ref

    @pl.when(pl.program_id(1) < used_ref[0])
    def _():
        u = jnp.maximum(jnp.dot(x_ref[...], w_ref[0, 0],
                                preferred_element_type=jnp.float32), 0.0)
        o_ref[...] = (u * u).astype(o_ref.dtype)


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
    tn = _tile_n(N, K * len(weights), weights[0].dtype.itemsize)
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


def grouped_experts(xs, w_in, w_down, layer, tile_expert, used, tile_m: int):
    """``act(xs W_in[e]) W_down[e]`` for rows sorted into tiles of
    ``tile_m``, tile ``t`` multiplied with expert ``tile_expert[t]`` of
    layer ``layer`` of the stacked ``(layers, experts, in, out)`` weights;
    only the first ``used`` tiles are computed or fetched. ``w_in`` is
    ``(w_gate, w_up)`` for a gated-SiLU expert (``silu(x Wg) * (x Wu)``,
    kernel ``moe_experts_gate_up``) or ``(w_up,)`` for a squared-ReLU one
    (``relu(x Wu)^2``, kernel ``moe_experts_up``)."""
    with jax.named_scope("moe_experts"):
        if len(w_in) == 2:
            mid = _grouped_call(_gate_up_kernel, "moe_experts_gate_up", xs,
                                w_in, layer, tile_expert, used, tile_m)
        else:
            mid = _grouped_call(_up_kernel, "moe_experts_up", xs, w_in,
                                layer, tile_expert, used, tile_m)
        return _grouped_call(_down_kernel, "moe_experts_down", mid,
                             (w_down,), layer, tile_expert, used, tile_m)


def _act(activation, w_in, x, hi):
    """One expert's hidden row, float32: ``w_in`` as in
    :func:`grouped_experts`."""
    dot = lambda w: jnp.dot(x, w.astype(jnp.float32), precision=hi)
    if activation == "swiglu":
        return jax.nn.silu(dot(w_in[0])) * dot(w_in[1])
    return jnp.square(jnp.maximum(dot(w_in[0]), 0.0))


class HeldExpertsMLP:
    """Dropless top-``top_k`` sigmoid-routed experts, the part of one chip
    that holds the experts ``held`` (ids in the published numbering) of
    ``num_experts`` (section comment above). ONE layer whose form is its
    parameters:

    - ``activation``: ``"swiglu"`` (``(silu(x Wg) * (x Wu)) Wd``, three
      matrices an expert) or ``"relu2"`` (``relu(x Wu)^2 Wd``, two);
    - ``select_bias``: a per-expert bias (``router_bias``) added
      to the scores for the CHOICE of the ``top_k`` and not for their
      weights (DeepSeek-V3's);
    - ``scaling``: the routed sum times this factor;
    - ``latent_size``: the routed experts work in a latent of this width:
      ``l = x W_latent_down`` goes in, the weighted sum of the held picks
      comes out and ``W_latent_up`` is applied to that PARTIAL sum (the
      projections are linear, so the chips' parts still add); router and
      shared expert read the full ``hidden_size``;
    - the shared part: ``num_shared`` experts of the routed width and form
      whose MEAN is added for every token, riding as more tiles of the
      grouped product; or, with ``shared_size``, one shared expert of that
      width on the full hidden size as its own two (three, gated) dense
      products, added whole.

    The router keeps its published width: a token's weights are its
    ``top_k`` picks' sigmoid scores over ALL experts, normalised over all
    ``top_k`` picks; the picks that are held add ``w_e E_e(x)``, the
    others nothing. Parameters (``init``): ``router`` ``(hidden,
    num_experts)``, ``w_up`` (and ``w_gate``) ``(n, d, expert_size)``,
    ``w_down`` ``(n, expert_size, d)`` with ``d`` the latent or hidden size
    and ``n = len(held) + num_shared``, the held experts in the order of
    ``held``, then the riding shared ones; with their options
    ``router_bias``, ``w_latent_down``, ``w_latent_up``, ``shared_up``
    (``shared_gate``), ``shared_down``.

    ``__call__(params, x, valid=None, layer=None)``: ``x`` ``(tokens,
    hidden)``; ``valid`` ``(tokens,)`` bool masks padding and idle slots
    out of the routing (no row, no count); with ``layer`` the stacked expert
    arrays (:attr:`stacked`) carry a leading layer axis and ``layer`` (int32
    scalar, traced in the layer scan) says which to read — everything else
    is that layer's own; ``held`` as in :meth:`route`. Returns ``(out
    (tokens, hidden), {"load": (len(held),) int32 assignments by held
    expert, "no_held_pick": int32 valid tokens none of whose picks is
    held})``.

    ``axis_name``: inside ``shard_map`` over the chips that share the
    layer, each passing its own ``held`` weights, the parts are summed
    over the axis (the layer's one exchange, an all-reduce, since every
    chip holds every token) and what every chip computes alike (the shared
    part) is counted once. On one chip there is no exchange."""

    def __init__(self, hidden_size: int, expert_size: int, num_experts: int,
                 top_k: int, held: Sequence[int], num_shared: int = 0,
                 axis_name: Optional[str] = None,
                 params_dtype=jnp.bfloat16, init_std: float = 0.02,
                 use_pallas: bool = True, activation: str = "swiglu",
                 select_bias: bool = False, scaling: float = 1.0,
                 latent_size: Optional[int] = None,
                 shared_size: Optional[int] = None):
        held = tuple(int(e) for e in held)
        if len(set(held)) != len(held) or not held \
                or not all(0 <= e < num_experts for e in held):
            raise ValueError(f"held experts {held} are not distinct ids "
                             f"below {num_experts}")
        if not 0 < top_k <= num_experts:
            raise ValueError(f"top_k {top_k} outside (0, {num_experts}]")
        if activation not in ACTIVATIONS:
            raise ValueError(f"activation {activation!r} not among "
                             f"{ACTIVATIONS}")
        if shared_size and num_shared:
            raise ValueError("the shared part either rides in the grouped "
                             "product (num_shared) or is one expert of its "
                             "own width (shared_size), not both")
        self.hidden_size, self.expert_size = hidden_size, expert_size
        self.num_experts, self.top_k = num_experts, top_k
        self.held, self.num_shared = held, int(num_shared)
        self.axis_name = axis_name
        self.params_dtype = params_dtype
        self.init_std = init_std
        self.use_pallas = use_pallas
        self.activation = activation
        self.select_bias = bool(select_bias)
        self.scaling = float(scaling)
        self.latent_size = latent_size
        self.shared_size = shared_size

    @property
    def num_local(self) -> int:
        return len(self.held) + self.num_shared

    @property
    def stacked(self) -> Tuple[str, ...]:
        """The per-expert arrays: read by layer index where they lie."""
        return ("w_gate", "w_up", "w_down") if self.activation == "swiglu" \
            else ("w_up", "w_down")

    def param_shapes(self) -> dict:
        n, h, f = self.num_local, self.hidden_size, self.expert_size
        d = self.latent_size or h
        gated = self.activation == "swiglu"
        out = {"router": (h, self.num_experts), "w_up": (n, d, f),
               "w_down": (n, f, d)}
        if gated:
            out["w_gate"] = (n, d, f)
        if self.select_bias:
            out["router_bias"] = (self.num_experts,)
        if self.latent_size:
            out["w_latent_down"] = (h, d)
            out["w_latent_up"] = (d, h)
        if self.shared_size:
            out["shared_up"] = (h, self.shared_size)
            out["shared_down"] = (self.shared_size, h)
            if gated:
                out["shared_gate"] = (h, self.shared_size)
        return out

    def init(self, key: jax.Array) -> dict:
        out = {}
        for i, (name, shape) in enumerate(sorted(
                self.param_shapes().items())):
            out[name] = (self.init_std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            ).astype(self.params_dtype)
        return out

    def tile_m(self, tokens: int) -> int:
        """Rows a tile: about the rows an expert gets when the picks are
        spread evenly, within [32, 256] (a tile is one expert's, so its
        last one is padded: small tiles where the rows are few)."""
        per = max(1, tokens * self.top_k // self.num_experts)
        return int(min(256, max(32, 1 << (per - 1).bit_length())))

    def route(self, router, x, valid=None, held=None, bias=None):
        """``(local (tokens, picks) int32, weight (tokens, picks) f32)``:
        per token its routed picks then the riding shared experts,
        ``local`` the index into this chip's expert stack or -1 (not held,
        or the token is not ``valid``). ``held``: the ids as a traced
        ``(len(held),)`` array in the place of the constructor's (a chip
        that learns its experts from its place on the axis). ``bias``: the
        selection bias, where the layer has one."""
        T = x.shape[0]
        ids = np.asarray(self.held, np.int32) if held is None else held
        scores = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        if bias is None:
            top_s, top_e = jax.lax.top_k(scores, self.top_k)
        else:
            _, top_e = jax.lax.top_k(
                scores + bias.astype(jnp.float32)[None, :], self.top_k)
            top_s = jnp.take_along_axis(scores, top_e, axis=-1)
        weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True) \
            * self.scaling
        match = top_e[..., None] == ids[None, None, :]
        local = jnp.where(jnp.any(match, axis=-1),
                          jnp.argmax(match, axis=-1), -1).astype(jnp.int32)
        if self.num_shared:
            ns = self.num_shared
            local = jnp.concatenate([local, jnp.broadcast_to(
                len(self.held) + jnp.arange(ns, dtype=jnp.int32),
                (T, ns))], axis=1)
            weight = jnp.concatenate(
                [weight, jnp.full((T, ns), self._once() / ns, jnp.float32)],
                axis=1)
        if valid is not None:
            local = jnp.where(valid[:, None], local, -1)
        return local, weight

    def _once(self) -> float:
        """The share of what every chip of the axis computes alike that
        this chip adds, so that the sum over the axis counts it once."""
        if self.axis_name is None:
            return 1.0
        return 1.0 / jax.lax.axis_size(self.axis_name)

    def __call__(self, params: dict, x: jnp.ndarray,
                 valid: Optional[jnp.ndarray] = None, layer=None,
                 held=None):
        T = x.shape[0]
        nh = len(self.held)
        local, weight = self.route(params["router"], x, valid, held,
                                   params.get("router_bias"))
        routed = local[:, :self.top_k]
        onehot_r = (routed[..., None] == jnp.arange(nh)).astype(jnp.int32)
        ok = jnp.ones((T,), bool) if valid is None else valid
        stats = {"load": jnp.sum(onehot_r, axis=(0, 1)),
                 "no_held_pick": jnp.sum(
                     ok & ~jnp.any(routed >= 0, axis=1)).astype(jnp.int32)}
        stack = [params[k] for k in self.stacked]
        if layer is None:
            stack = [w[None] for w in stack]
            layer = 0
        layer = jnp.asarray(layer, jnp.int32)
        w_in, w_down = tuple(stack[:-1]), stack[-1]
        rows = x
        if self.latent_size:
            rows = jnp.dot(x, params["w_latent_down"],
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)
        if self.use_pallas:
            out = self._sorted_product(rows, local, weight, w_in, w_down,
                                       layer)
        else:
            out = self._dense_product(rows, local, weight,
                                      [w[layer] for w in w_in],
                                      w_down[layer])
        if self.latent_size:
            out = jnp.dot(out.astype(x.dtype), params["w_latent_up"],
                          preferred_element_type=jnp.float32)
        if self.shared_size:
            out = out + self._once() * self._shared(params, x)
        if self.axis_name is not None:
            out = jax.lax.psum(out, self.axis_name)
        return out.astype(x.dtype), stats

    def _shared(self, params, x):
        """The shared expert of its own width: dense products over every
        token, float32 out."""
        dot = lambda a, w: jnp.dot(a, w, preferred_element_type=jnp.float32)
        if self.activation == "swiglu":
            mid = jax.nn.silu(dot(x, params["shared_gate"])) \
                * dot(x, params["shared_up"])
        else:
            mid = jnp.square(jnp.maximum(dot(x, params["shared_up"]), 0.0))
        return dot(mid.astype(x.dtype), params["shared_down"])

    def _dense_product(self, x, local, weight, w_in, w_down):
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
            mid = _act(self.activation, [w[e] for w in w_in], x32, hi)
            out = out + per[:, e:e + 1] * jnp.dot(
                mid, w_down[e].astype(jnp.float32), precision=hi)
        return out

    def _sorted_product(self, x, local, weight, w_in, w_down, layer):
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
        ys = grouped_experts(xs, w_in, w_down, layer, tile_expert, used, tm)
        # each token gathers its own picks' rows back: a gather, where a
        # scatter-add over the rows would serialise
        held = (flat >= 0).reshape(T, picks)
        got = jnp.take(ys, jnp.minimum(dest, M - 1).reshape(T, picks),
                       axis=0).astype(jnp.float32)
        return jnp.sum(jnp.where(held[..., None], got, 0.0)
                       * weight[..., None], axis=1)

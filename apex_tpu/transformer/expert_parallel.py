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
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from apex_tpu.transformer.tensor_parallel.layers import init_method_normal
from jax.lax import axis_size as _axis_size

__all__ = ["ExpertParallelMLP"]


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

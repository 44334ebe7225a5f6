"""Operations and bytes the ``cohere2_moe`` ALGORITHM needs for the share
one chip holds, from the cell's shapes and a step's routing alone
(``benchmark/counts.py`` says what a count is; the chip's peaks and
``roofline_seconds`` are that file's for every family).

A step's routing is ``expert_stats``: per layer, in the order the layers
run, the assignments that landed on each held expert and last the tokens
with no held pick — what the program counts in its step and the family
keeps with the call (``step_facts``). Where a call has none (the run's
last), the even spread stands in: ``tokens * picks * held / width``.
"""

import numpy as np

from benchmark.families.cohere2_moe_reference import (kinds, layer_types,
                                                      local_experts)


def _expert_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def _attention_params(cfg):
    H, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    return H * (q + 2 * kv) + q * H


def n_params(cfg):
    """Parameters stored on the chip: the held share of every layer and
    the chip's rows of the tied embedding."""
    H, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    per_layer = (_attention_params(cfg) + H * cfg["router_width"] + H
                 + local_experts(cfg) * _expert_params(cfg))
    return cfg["vocab_size"] * H + H + L * per_layer


def weight_bytes(cfg):
    return 2 * n_params(cfg)                 # stored bfloat16


def _assignments(cfg, tokens, expert_stats):
    """``(rows through the expert product, expert matrices touched)`` of
    one step over all layers: held routed assignments plus every token
    through every shared expert."""
    L, ns = cfg["num_hidden_layers"], cfg["num_shared_experts"]
    nh = len(cfg["held_experts"])
    if expert_stats is None:
        per_expert = tokens * cfg["num_experts_per_tok"] \
            / cfg["router_width"]
        routed = L * nh * per_expert
        touched = L * nh * (1.0 - (1.0 - cfg["num_experts_per_tok"]
                                   / cfg["router_width"]) ** tokens)
    else:
        load = np.asarray(expert_stats)[:, :nh]
        routed, touched = float(load.sum()), float((load > 0).sum())
    return routed + L * ns * tokens, touched + L * ns


def _attended(cfg, contexts):
    """Cached positions a step's rows read, over all layers: a window
    layer reads at most ``sliding_window`` of them, the row's own
    included."""
    total = 0.0
    for kind in layer_types(cfg):
        for c in contexts:
            total += min(c, cfg["sliding_window"]) \
                if kind == "sliding_attention" else c
    return total


def moe_experts_work(cfg, contexts, tokens=None, expert_stats=None, **_):
    """The expert product (``moe_experts_gate_up`` + ``moe_experts_down``)
    of one decode step (``tokens`` None: one row a context) or one
    prefill: 2 FLOPs per parameter of an expert per row, and the bytes
    that must move: every touched expert's matrices once, the rows in
    and out and the gated intermediate once each way (bfloat16)."""
    rows, touched = _assignments(
        cfg, len(contexts) if tokens is None else tokens, expert_stats)
    H, F = cfg["hidden_size"], cfg["intermediate_size"]
    return {"flops": 2.0 * _expert_params(cfg) * rows,
            "bytes": 2.0 * _expert_params(cfg) * touched
            + 2.0 * rows * (2 * H + 2 * F)}


def decode_step_flops(cfg, contexts, expert_stats=None, **_):
    """One decode step over the slots whose cached lengths are
    ``contexts``: projections, router and the tied head per token, the
    expert product by its assignments, ``4 heads d`` per attended position
    and layer."""
    n = len(contexts)
    H, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense = 2.0 * n * (L * (_attention_params(cfg) + H * cfg["router_width"])
                       + cfg["vocab_size"] * H)
    attn = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * _attended(cfg, contexts)
    return dense + attn + moe_experts_work(
        cfg, contexts, expert_stats=expert_stats)["flops"]


def prefill_flops(cfg, tokens, expert_stats=None, **_):
    """One prompt of ``tokens`` positions, the head on the last only; a
    window layer's rows read at most ``sliding_window`` positions."""
    H, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    dense = 2.0 * tokens * L * (_attention_params(cfg)
                                + H * cfg["router_width"]) \
        + 2.0 * cfg["vocab_size"] * H
    attn = 0.0
    W = cfg["sliding_window"]
    for kind in layer_types(cfg):
        if kind == "sliding_attention" and tokens > W:
            pairs = W * (W + 1) / 2.0 + (tokens - W) * W
        else:
            pairs = tokens * (tokens + 1) / 2.0
        attn += 4.0 * hd * pairs
    return dense + attn + moe_experts_work(
        cfg, [], tokens=tokens, expert_stats=expert_stats)["flops"]


def paged_decode_work(cfg, contexts, kv_bytes=2, **_):
    """The paged decode kernel over one step, all layers: each slot's
    cached K and V of the chip's KV heads read once (a window layer's last
    ``sliding_window`` positions only), ``4 heads d`` FLOPs a position."""
    positions = _attended(cfg, contexts)
    d = cfg["head_dim"]
    return {"flops": 4.0 * cfg["num_attention_heads"] * d * positions,
            "bytes": 2.0 * cfg["num_key_value_heads"] * d * positions
            * kv_bytes}


def block_bytes(cfg, kind, block_size, itemsize=2):
    """One pool block of ``kind``: K and V of the kind's layers."""
    return (2 * kinds(cfg)[kind] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * block_size * itemsize)


def blocks_filled(cfg, contexts, block_size):
    """Blocks a step's slots hold by kind: all of a context on a full
    layer, the window's on a window layer."""
    out = dict.fromkeys(kinds(cfg), 0)
    W = cfg["sliding_window"]
    for kind in out:
        for c in contexts:
            first = max(0, c - W + 1) // block_size \
                if kind == "sliding_attention" else 0
            out[kind] += -(-c // block_size) - first
    return out

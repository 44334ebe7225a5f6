"""Operations and bytes the ``nemotron_h`` ALGORITHM needs for the share one
chip holds, from the cell's shapes and a step's routing alone
(``benchmark/counts.py`` says what a count is; the chip's peaks and
``roofline_seconds`` are that file's for every family).

A step's routing is ``expert_stats``: per ``E`` layer, in the order the
layers run, the assignments that landed on each held expert and last the
tokens with no held pick: what the program counts in its step and the
family keeps with the call (``step_facts``). Where a call has none (the
run's last), the even spread stands in: ``tokens * picks * held / width``.
"""

import numpy as np

from benchmark.families.nemotron_h_reference import (kinds, mamba_sizes,
                                                     weight_shapes)


def _expert_params(cfg):
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def _dense_params(cfg):
    """``{kind: parameters of one layer's matrices that every token
    multiplies}``: the three-axis tensors of the layout (a layer axis, in,
    out); the routed experts' stacks have four."""
    return {kind: sum(int(np.prod(s[1:])) for s in shapes.values()
                      if len(s) == 3)
            for kind, shapes in weight_shapes(cfg)["layers"].items()}


def n_params(cfg):
    """Parameters stored on the chip: the held share of every layer, the
    chip's rows of the embedding and of the untied head."""
    shapes = weight_shapes(cfg)
    flat = [shapes["embedding"], shapes["lm_head"], shapes["final_norm"]] \
        + [s for lp in shapes["layers"].values() for s in lp.values()]
    return sum(int(np.prod(s)) for s in flat)


def weight_bytes(cfg):
    return 2 * n_params(cfg)                 # stored bfloat16


def _dense_flops_per_token(cfg):
    """2 x the matrix parameters every token multiplies, all layers: the
    projections, router, latent and shared expert (not the routed experts,
    not the head)."""
    return 2.0 * sum(kinds(cfg)[kind] * n
                     for kind, n in _dense_params(cfg).items())


def _assignments(cfg, tokens, expert_stats):
    """``(rows through the expert product, expert matrices touched)`` of
    one call over all ``E`` layers: the held routed assignments."""
    L, nh = kinds(cfg).get("moe", 0), cfg["n_routed_experts"]
    if expert_stats is None:
        share = cfg["num_experts_per_tok"] / cfg["router_width"]
        return (L * nh * tokens * share,
                L * nh * (1.0 - (1.0 - share) ** tokens))
    load = np.asarray(expert_stats)[:, :nh]
    return float(load.sum()), float((load > 0).sum())


def moe_experts_work(cfg, contexts, tokens=None, expert_stats=None, **_):
    """The routed product (``moe_experts_up`` + ``moe_experts_down``) of one
    decode step (``tokens`` None: one row a context) or one prefill: 2
    FLOPs per parameter of an expert per row, and the bytes that must move:
    every touched expert's two matrices once, the latent rows in and out
    and the hidden rows once each way (bfloat16)."""
    rows, touched = _assignments(
        cfg, len(contexts) if tokens is None else tokens, expert_stats)
    lat, F = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    return {"flops": 2.0 * _expert_params(cfg) * rows,
            "bytes": 2.0 * _expert_params(cfg) * touched
            + 2.0 * rows * (2 * lat + 2 * F)}


def mamba2_scan_work(cfg, contexts=(), tokens=None, **_):
    """The chunked scan (``mamba2_chunk_scan``) of one prefill over the
    prompt's OWN ``tokens`` (never a bucket's padding), all ``M`` layers; a
    decode step runs none of it (``tokens`` None: nothing). Per chunk of Q
    tokens: ``C B^T`` a group (2 Q^2 N), and a head the decay-masked product
    with the inputs, the carried state's read-out and the chunk's own state
    (2 Q^2 P + 2 Q P N twice). Bytes: x in (bfloat16) and y out (float32),
    B and C, dt and the two layouts of its running sum, the final state."""
    if tokens is None:
        return {"flops": 0.0, "bytes": 0.0}
    L = kinds(cfg).get("mamba", 0)
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, Q = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    chunks = -(-tokens // Q)
    per_chunk = G * 2.0 * Q * Q * N \
        + H * (2.0 * Q * Q * P + 2 * 2.0 * Q * P * N)
    inner, _, gn = mamba_sizes(cfg)
    per_token = inner * (2 + 4) + 2 * gn * 2 + H * 4 * 3
    return {"flops": L * chunks * per_chunk,
            "bytes": L * (tokens * per_token + H * P * N * 4.0)}


def _attended(cfg, contexts):
    return float(sum(contexts)) * kinds(cfg).get("attention", 0)


def paged_decode_work(cfg, contexts, kv_bytes=2, **_):
    """The paged decode kernel over one step, the ``*`` layers: each slot's
    cached K and V of the chip's KV head read once, ``4 heads d`` FLOPs a
    position."""
    positions = _attended(cfg, contexts)
    d = cfg["head_dim"]
    return {"flops": 4.0 * cfg["num_attention_heads"] * d * positions,
            "bytes": 2.0 * cfg["num_key_value_heads"] * d * positions
            * kv_bytes}


def _state_flops_per_token(cfg):
    """The recurrence's own arithmetic a token, all ``M`` layers: decay,
    outer product and read-out over the ``(H, P, N)`` state."""
    return 6.0 * kinds(cfg).get("mamba", 0) * cfg["mamba_num_heads"] \
        * cfg["mamba_head_dim"] * cfg["ssm_state_size"]


def decode_step_flops(cfg, contexts, expert_stats=None, **_):
    """One decode step over the slots whose cached lengths are
    ``contexts``: the dense products and the head per token, the routed
    product by its assignments, the state update, ``4 heads d`` per attended
    position of the ``*`` layers."""
    n = len(contexts)
    dense = n * (_dense_flops_per_token(cfg) + _state_flops_per_token(cfg)
                 + 2.0 * cfg["vocab_size"] * cfg["hidden_size"])
    attn = 4.0 * cfg["num_attention_heads"] * cfg["head_dim"] \
        * _attended(cfg, contexts)
    return dense + attn + moe_experts_work(
        cfg, contexts, expert_stats=expert_stats)["flops"]


def prefill_flops(cfg, tokens, expert_stats=None, **_):
    """One prompt of ``tokens`` positions, the head on the last only."""
    hd = cfg["num_attention_heads"] * cfg["head_dim"]
    dense = tokens * _dense_flops_per_token(cfg) \
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    attn = 4.0 * hd * tokens * (tokens + 1) / 2.0 \
        * kinds(cfg).get("attention", 0)
    return dense + attn + mamba2_scan_work(cfg, tokens=tokens)["flops"] \
        + moe_experts_work(cfg, [], tokens=tokens,
                           expert_stats=expert_stats)["flops"]


def block_bytes(cfg, block_size, itemsize=2):
    """One pool block: K and V of the ``*`` layers."""
    return (2 * kinds(cfg).get("attention", 0) * cfg["num_key_value_heads"]
            * cfg["head_dim"] * block_size * itemsize)


def blocks_filled(contexts, block_size):
    return sum(-(-c // block_size) for c in contexts)


def state_bytes_per_slot(cfg, conv_itemsize=2):
    """What one slot's recurrent state takes, all ``M`` layers: the SSM
    state float32 and the conv's tail."""
    _, conv, _ = mamba_sizes(cfg)
    return kinds(cfg).get("mamba", 0) * (
        cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
        * cfg["ssm_state_size"] * 4
        + (cfg["conv_kernel"] - 1) * conv * conv_itemsize)

"""Operations and bytes the ``mistral4`` ALGORITHM needs for the share one chip
holds, from the cell's shapes and a step's facts alone
(``benchmark/counts.py`` says what a count is; the chip's peaks and
``roofline_seconds`` are that file's for every family).

A latent layer caches one row a position for all heads, ``kv_lora_rank +
qk_rope_head_dim`` wide: 320 values, 640 B in bfloat16 (a chip lays the
row over 384 lanes; the ALGORITHM needs 640 B, which is what is counted).
"""

import numpy as np

from benchmark.families import cohere2_moe_counts
from benchmark.families.mistral4_reference import (KIND, held_experts,
                                                   weight_shapes)


def n_params(cfg):
    """Parameters stored on the chip: every layer's attention, router,
    shared expert and held experts, the chip's rows of the embedding and of
    the untied head."""
    shapes = weight_shapes(cfg)
    flat = [shapes["embedding"], shapes["lm_head"], shapes["final_norm"]] \
        + list(shapes["layers"][KIND].values())
    return sum(int(np.prod(s)) for s in flat)


def weight_bytes(cfg):
    return 2 * n_params(cfg)                 # stored bfloat16


def layer_params(cfg):
    """``{part: parameters of one layer}``: ``attention`` (the latent
    projections, their norms, ``Wo``), ``shared`` (the shared expert),
    ``router`` (the router, its bias and the layer's two norms) and
    ``routed`` (the held experts)."""
    per = {n: int(np.prod(s[1:]))
           for n, s in weight_shapes(cfg)["layers"][KIND].items()}
    attention = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "w_uk",
                 "w_uv", "wo")
    shared = ("shared_gate", "shared_up", "shared_down")
    routed = ("w_gate", "w_up", "w_down")
    return {"attention": sum(per[n] for n in attention),
            "shared": sum(per[n] for n in shared),
            "routed": sum(per[n] for n in routed),
            "router": sum(v for n, v in per.items()
                          if n not in attention + shared + routed)}


def row_width(cfg):
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def mla_decode_work(cfg, contexts, latent_positions=None, itemsize=2, **_):
    """The absorbed decode kernel (``mla_decode_attention``) over one step,
    all layers: each live position's row read ONCE at its true width (the
    program's own count, ``latent_positions``; where a call has none, one
    more than each slot's cached positions), each served slot's absorbed
    query in and latent context out; ``2 heads (row + latent)`` FLOPs a
    position (the score against the whole row, the context over the
    latent)."""
    L, nh = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    W, kr = row_width(cfg), cfg["kv_lora_rank"]
    positions = float(sum(c + 1 for c in contexts)
                      if latent_positions is None else latent_positions)
    return {"flops": L * 2.0 * nh * (W + kr) * positions,
            "bytes": L * (positions * W + len(contexts) * nh * (W + kr))
            * itemsize}


def mla_prefill_work(cfg, contexts=(), tokens=None, itemsize=2, **_):
    """The prefill's attention kernel (the flash forward) over the prompt's
    OWN ``tokens`` (never a bucket's padding), all layers; a decode step
    runs none of it (``tokens`` None: nothing). The causal half of the
    scores and of the weighted values, every head at query-key width
    ``nope + rope`` and value width ``v``; the bytes of the query, key and
    value in and the context out."""
    if tokens is None:
        return {"flops": 0.0, "bytes": 0.0}
    L, nh = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    pairs = tokens * (tokens + 1) / 2.0
    return {"flops": L * 2.0 * nh * (dqk + dv) * pairs,
            "bytes": L * tokens * nh * (2 * dqk + 2 * dv) * itemsize}


def moe_experts_work(cfg, contexts, tokens=None, expert_stats=None, **_):
    """The routed expert product (``moe_experts_gate_up`` +
    ``moe_experts_down``) of one decode step or one prefill: the same
    kernels and count as the ``cohere2_moe`` family's, at this
    configuration's expert width and held experts. The shared expert is a
    dense product outside these kernels and is not counted."""
    return cohere2_moe_counts.moe_experts_work(
        dict(cfg, intermediate_size=cfg["moe_intermediate_size"],
             held_experts=held_experts(cfg), num_shared_experts=0),
        contexts, tokens=tokens, expert_stats=expert_stats)


def mla_projection_flops_per_token(cfg):
    """2 x the attention's matrix parameters, one layer: what a token's
    latent mixer multiplies outside the scores."""
    return 2.0 * layer_params(cfg)["attention"]


def block_bytes(cfg, block_size, itemsize=2):
    """One pool block: the latent rows of every layer."""
    return cfg["num_hidden_layers"] * block_size * row_width(cfg) * itemsize


def blocks_filled(contexts, block_size):
    return sum(-(-c // block_size) for c in contexts)

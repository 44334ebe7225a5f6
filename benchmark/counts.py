"""Operations and bytes the ALGORITHM needs, from the cell's shapes alone.

A kernel's roofline share reads the same work whatever later implements it:
nothing here looks at the program. Recomputed operations never count.
``cfg`` is a configuration file's dict (the model's own ``config.json``
keys).
"""

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The chip's published peaks; an unknown kind is an error, never a
    default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "benchmark/peaks.json")
    return table[device_kind]


def n_params(cfg):
    """Parameters of the GPT-2 block stack with a tied head: embeddings,
    per layer 4 matrices + their biases + 2 norms, the final norm."""
    h, L, V, P = (cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"],
                  cfg["n_positions"])
    f = cfg.get("n_inner") or 4 * h
    per_layer = (3 * h * h + 3 * h) + (h * h + h) + (h * f + f) \
        + (f * h + h) + 4 * h
    return V * h + P * h + L * per_layer + 2 * h


def train_flops_per_token(cfg, seq):
    """Forward + backward, no recompute: 6 FLOPs per parameter of the
    matrices (the position table is a lookup) plus ``6 L h seq`` for causal
    attention. ``bench.py`` counts ``6 N + 12 L h seq`` (the llm.c /
    PaLM-appendix convention, full square of scores): the causal algorithm
    needs half of that square, and a share of a peak may not count work
    the algorithm does not need."""
    matrices = n_params(cfg) - cfg["n_positions"] * cfg["n_embd"]
    return 6.0 * matrices + 6.0 * cfg["n_layer"] * cfg["n_embd"] * seq


def flash_train_work(cfg, rows, seq, dtype_bytes=2):
    """Causal attention forward + backward over ``rows`` sequences, per
    layer stack: FLOPs (forward 2 matrix products, backward 4 — dV, dP,
    dQ, dK; the backward's recomputation of the scores is not counted;
    the causal half only) and the bytes that must move (forward reads
    q, k, v and writes o; backward reads q, k, v, o, do and writes dq, dk,
    dv)."""
    L, nh = cfg["n_layer"], cfg["n_head"]
    d = cfg["n_embd"] // nh
    per_product = 2.0 * rows * nh * seq * seq * d * 0.5
    flops = L * 6.0 * per_product
    tensor = rows * nh * seq * d * dtype_bytes
    return {"flops": flops, "bytes": L * (4 + 8) * tensor}


def decode_step_flops(cfg, contexts):
    """One decode step over the slots whose context lengths (tokens already
    cached, the new one included) are ``contexts``: 2 FLOPs per parameter
    of the matrices (the position table is a lookup, the tied head is a
    product) and ``4 h`` per cached position and layer for attention."""
    h, L = cfg["n_embd"], cfg["n_layer"]
    matrices = n_params(cfg) - cfg["n_positions"] * h
    return sum(2.0 * matrices + 4.0 * L * h * c for c in contexts)


def prefill_flops(cfg, tokens):
    """One prompt of ``tokens`` positions through the stack, the head on
    the last position only."""
    h, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    matrices = n_params(cfg) - cfg["n_positions"] * h - V * h
    return 2.0 * matrices * tokens + 2.0 * V * h \
        + 4.0 * L * h * tokens * tokens * 0.5


def paged_decode_work(cfg, contexts, kv_bytes=2):
    """The paged decode kernel over one step, all layers: it must read each
    slot's cached K and V once (``2 * context * h`` elements a layer) and
    does ``4 h`` FLOPs per cached position."""
    h, L = cfg["n_embd"], cfg["n_layer"]
    positions = float(sum(contexts))
    return {"flops": 4.0 * L * h * positions,
            "bytes": 2.0 * L * h * positions * kv_bytes}


def roofline_seconds(work, peak):
    """The least time the chip could take and which bound it is."""
    t_flops = work["flops"] / peak["bf16_flops_per_s"]
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")

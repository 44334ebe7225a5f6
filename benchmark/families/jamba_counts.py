"""Operations and bytes the ``jamba`` ALGORITHM needs, from the cell's shapes
alone (``benchmark/counts.py`` says what a count is; the chip's peaks and
``roofline_seconds`` are that file's for every family). The model is held
whole: nothing here is a share.
"""

import numpy as np

from benchmark.families.jamba_reference import (ATTENTION, MAMBA, kinds,
                                                sizes, weight_shapes)

# what the recurrence costs a state entry a token: the decay's product and
# exponential, the decayed state, the input's product and its sum, the
# read-out's product and its sum
SCAN_OPS_PER_ENTRY = 7.0


def n_params(cfg):
    """Parameters stored on the chip: every layer whole, the embedding once
    (the head is tied to it)."""
    shapes = weight_shapes(cfg)
    flat = [shapes["embedding"], shapes["final_norm"]] \
        + [s for lp in shapes["layers"].values() for s in lp.values()]
    return sum(int(np.prod(s)) for s in flat)


def weight_bytes(cfg):
    return 2 * n_params(cfg)                 # stored bfloat16


def _matrix_flops_per_token(cfg):
    """2 x the matrix parameters every token multiplies, all layers (the
    three-axis tensors of the layout but the conv's taps and ``A_log``; not
    the head)."""
    shapes = weight_shapes(cfg)["layers"]
    return 2.0 * sum(
        int(np.prod(s)) for lp in shapes.values() for name, s in lp.items()
        if len(s) == 3 and name not in ("conv_w", "A_log"))


def _entries(cfg):
    """State entries of all Mamba layers: what a token's recurrence
    touches."""
    E, N, _, _ = sizes(cfg)
    return kinds(cfg).get(MAMBA, 0) * E * N


def selective_scan_work(cfg, contexts=(), tokens=None, **_):
    """The scan kernel (``mamba1_selective_scan``) of one prefill over the
    prompt's OWN ``tokens`` (never a bucket's padding), all Mamba layers; a
    decode step runs none of it (``tokens`` None: nothing). Per token and
    layer ``SCAN_OPS_PER_ENTRY`` operations on each of the ``N E`` state
    entries; the bytes of ``delta``, ``delta u`` in and ``y`` out (float32,
    ``E`` each) and of ``B`` and ``C``; the state (``N E`` float32) once a
    call."""
    if tokens is None:
        return {"flops": 0.0, "bytes": 0.0}
    E, N, _, _ = sizes(cfg)
    L = kinds(cfg).get(MAMBA, 0)
    return {"flops": SCAN_OPS_PER_ENTRY * _entries(cfg) * tokens,
            "bytes": L * (tokens * (3 * E + 2 * N) * 4.0 + N * E * 4.0)}


def decode_update_work(cfg, contexts, **_):
    """The one-token state update of one decode step, all Mamba layers: a
    LIVE slot's ``(N, E)`` float32 rows read once and written once (an idle
    slot's need not move), ``SCAN_OPS_PER_ENTRY`` operations an entry."""
    live = len(contexts)
    return {"flops": SCAN_OPS_PER_ENTRY * _entries(cfg) * live,
            "bytes": 2.0 * 4.0 * _entries(cfg) * live}


def _attended(cfg, contexts):
    return float(sum(contexts)) * kinds(cfg).get(ATTENTION, 0)


def paged_decode_work(cfg, contexts, kv_bytes=2, **_):
    """The paged decode kernel over one step, the attention layers: each
    slot's cached K and V of the one KV head read once, ``4 heads d`` FLOPs
    a position."""
    positions = _attended(cfg, contexts)
    d = sizes(cfg)[3]
    return {"flops": 4.0 * cfg["num_attention_heads"] * d * positions,
            "bytes": 2.0 * cfg["num_key_value_heads"] * d * positions
            * kv_bytes}


def decode_step_flops(cfg, contexts, **_):
    """One decode step over the slots whose cached lengths are
    ``contexts``: the matrices and the head per token, the state update,
    ``4 heads d`` per attended position of the attention layers."""
    per_token = _matrix_flops_per_token(cfg) \
        + SCAN_OPS_PER_ENTRY * _entries(cfg) \
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    return len(contexts) * per_token \
        + paged_decode_work(cfg, contexts)["flops"]


def prefill_flops(cfg, tokens, **_):
    """One prompt of ``tokens`` positions, the head on the last only."""
    hd = cfg["num_attention_heads"] * sizes(cfg)[3]
    attn = 4.0 * hd * tokens * (tokens + 1) / 2.0 \
        * kinds(cfg).get(ATTENTION, 0)
    return tokens * _matrix_flops_per_token(cfg) \
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"] + attn \
        + selective_scan_work(cfg, tokens=tokens)["flops"]


def block_bytes(cfg, block_size, itemsize=2):
    """One pool block: K and V of the attention layers."""
    return (2 * kinds(cfg).get(ATTENTION, 0) * cfg["num_key_value_heads"]
            * sizes(cfg)[3] * block_size * itemsize)


def blocks_filled(contexts, block_size):
    return sum(-(-c // block_size) for c in contexts)


def state_bytes_per_slot(cfg, conv_itemsize=2):
    """What one slot's recurrent state takes, all Mamba layers: the SSM
    state float32 and the conv's tail."""
    E, N, _, _ = sizes(cfg)
    return kinds(cfg).get(MAMBA, 0) * (
        N * E * 4 + (cfg["mamba_d_conv"] - 1) * E * conv_itemsize)

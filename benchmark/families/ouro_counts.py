"""Operations and bytes the ``ouro`` ALGORITHM needs, from the cell's shapes
alone (``benchmark/counts.py`` says what a count is; the chip's peaks and
``roofline_seconds`` are that file's for every family). The model is held
whole: nothing here is a share. What the loop costs is counted where it
falls: every pass multiplies every layer's matrices again and keeps and
reads K/V of its own, so the layers' weights and the cached rows are counted
``total_ut_steps`` times a token; the embedding row and the head once.
"""

import numpy as np

from benchmark.families.ouro_reference import KIND, passes, weight_shapes


def layer_params(cfg):
    """One layer's parameters by part: the four attention matrices, the
    gated MLP's three, the four norm gains of the sandwich."""
    lp = {n: int(np.prod(s[1:]))
          for n, s in weight_shapes(cfg)["layers"][KIND].items()}
    part = lambda names: sum(lp[n] for n in names)
    return {"attention": part(("wq", "wk", "wv", "wo")),
            "mlp": part(("mlp_gate", "mlp_up", "mlp_down")),
            "norms": part(("norm", "mixer_out_norm", "ff_norm",
                           "ff_out_norm"))}


def n_params(cfg):
    """Parameters stored on the chip: every layer once (a pass reads the
    same weights), the embedding, the untied head, the final norm and the
    exit gate."""
    return sum(int(np.prod(s)) for s in _leaves(weight_shapes(cfg)))


def _leaves(tree):
    if isinstance(tree, dict):
        return [s for v in tree.values() for s in _leaves(v)]
    return [tree]


def weight_bytes(cfg):
    return 2 * n_params(cfg)                 # stored bfloat16


def _matrix_params(cfg):
    """The matrix parameters of the whole stack, one pass."""
    per = layer_params(cfg)
    return cfg["num_hidden_layers"] * (per["attention"] + per["mlp"])


def cached_layers(cfg):
    """K/V layers a position keeps: one a pass and a layer."""
    return passes(cfg) * cfg["num_hidden_layers"]


def kv_bytes_per_position(cfg, itemsize=2):
    return (2 * cached_layers(cfg) * cfg["num_key_value_heads"]
            * cfg["head_dim"] * itemsize)


def block_bytes(cfg, block_size, itemsize=2):
    """One pool block: K and V of every pass's every layer."""
    return block_size * kv_bytes_per_position(cfg, itemsize)


def blocks_filled(contexts, block_size):
    return sum(-(-c // block_size) for c in contexts)


def _attention_flops(cfg, positions):
    """``4 heads d`` FLOPs an attended position a cached layer (the scores
    and the weighted sum)."""
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"]
            * positions * cached_layers(cfg))


def paged_decode_work(cfg, contexts, kv_bytes=2, **_):
    """The paged decode kernel over one step, every call of it (a pass and
    a layer each, ``total_ut_steps * num_hidden_layers`` a step): each
    slot's cached K and V read once a call."""
    positions = float(sum(contexts))
    return {"flops": _attention_flops(cfg, positions),
            "bytes": positions * kv_bytes_per_position(cfg, kv_bytes)}


def decode_step_flops(cfg, contexts, **_):
    """One decode step over the slots whose cached lengths are
    ``contexts``: the layers' matrices once a pass a token, the head once a
    token, the attention of every pass."""
    per_token = 2.0 * passes(cfg) * _matrix_params(cfg) \
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]
    return len(contexts) * per_token \
        + paged_decode_work(cfg, contexts)["flops"]


def looped_decode_work(cfg, contexts, kv_bytes=2, **_):
    """The whole decode program of one step (``jit_decode_step``): the
    layers' weights streamed once a pass, the head and the norms once, each
    live slot's embedding row, every pass's K/V of the live slots read and
    its new row appended; FLOPs as :func:`decode_step_flops`."""
    live = len(contexts)
    H, V = cfg["hidden_size"], cfg["vocab_size"]
    per = layer_params(cfg)
    layers = cfg["num_hidden_layers"] * sum(per.values())
    weights = 2.0 * (passes(cfg) * layers + H * V + H)
    kv = kv_bytes_per_position(cfg, kv_bytes) * (float(sum(contexts)) + live)
    return {"flops": decode_step_flops(cfg, contexts),
            "bytes": weights + kv + 2.0 * live * H}


def prefill_flops(cfg, tokens, **_):
    """One prompt of ``tokens`` positions, every pass, causal attention,
    the head on the last position only."""
    attn = _attention_flops(cfg, tokens * (tokens + 1) / 2.0)
    return 2.0 * passes(cfg) * _matrix_params(cfg) * tokens + attn \
        + 2.0 * cfg["vocab_size"] * cfg["hidden_size"]

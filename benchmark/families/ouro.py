"""Family ``ouro``: all the harness knows of Ouro, a looped language model
(one stack of sandwich-normed rotary-attention + gated-MLP layers run
``total_ut_steps`` times over its own output) (``benchmark/README.md``, "A
family"): the program's model built from the configuration's own keys
(``apex_tpu.models.pattern_decoder``), the seed's weights handed to it as
the reference drew them, the plain reference and its controls
(``ouro_reference.py``), the operations and bytes the algorithm needs
(``ouro_counts.py``), and what a deployment holds on the chip. Serving
only: the family has no trainer.
"""

from benchmark.families import ouro_counts as counts  # noqa: F401
from benchmark.families import ouro_reference as reference
from benchmark.families.ouro_reference import (  # noqa: F401
    make_weights, seed_key)


def vocab(cfg):
    """The traffic draws its token ids below this: the whole vocabulary."""
    return cfg["vocab_size"]


def model(cfg):
    """The program's model at the configuration's sizes. Per-token depth
    (an exit gate that stops a token before the last pass) is refused: a
    later token would find no K/V of the passes an earlier one skipped."""
    from apex_tpu.models.pattern_decoder import (PatternDecoder,
                                                 PatternDecoderConfig)
    if cfg["early_exit_threshold"] < 1:
        raise ValueError(
            f"early_exit_threshold {cfg['early_exit_threshold']} < 1: "
            "per-token depth over a paged cache is not served")
    if cfg["rope_scaling"] is not None or cfg["use_sliding_window"]:
        raise ValueError("rope_scaling and a sliding window are not served")
    return PatternDecoder(PatternDecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=reference.layer_types(cfg),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], rope_theta=float(cfg["rope_theta"]),
        layer_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        block="sandwich", norm="rmsnorm", tie_embeddings=False,
        intermediate_size=cfg["intermediate_size"],
        loops=reference.passes(cfg)))


def serve_engine(cfg, eng, seed):
    """The paged engine over the pattern decoder: the reference's bfloat16
    tensors are the program's parameters as they are (same names, same
    layout), so the weights exist ONCE on the chip; one block pool whose
    blocks hold every pass's K/V, a few prefill buckets."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serving import ServingEngine

    # the model first: a program that cannot build it (one from before the
    # looped stack) fails here, before any weight is drawn
    program = model(cfg)
    lo, hi = seed_key(seed)
    params = jax.block_until_ready(
        jax.jit(lambda lo, hi: make_weights(cfg, lo, hi))(lo, hi))
    return ServingEngine(
        program, params, max_seqs=eng["max_seqs"], max_len=eng["max_len"],
        prefill_len=eng["prefill_buckets"],
        cache_dtype=jnp.dtype(eng["cache_dtype"]),
        speculate_k=eng["speculate_k"], block_size=eng["block_size"],
        num_blocks=eng["num_blocks"])


def step_facts(engine, sched):
    """The cached positions of each active slot, read before the call (the
    counts take a call's facts as keyword arguments)."""
    return {"contexts": [st.position for st in sched.active.values()]}


def held_bytes(cfg, eng, decode_calls):
    """The weights as stored and the KV blocks the traffic filled at the
    most, a block holding every pass's every layer."""
    block = eng["block_size"]
    most = max((counts.blocks_filled(step["contexts"], block)
                for _, _, step in decode_calls), default=0)
    per_block = counts.block_bytes(cfg, block)
    weights = counts.weight_bytes(cfg)
    pool = sum(eng["num_blocks"].values())
    return dict(
        kv_blocks_filled_at_most=most, kv_blocks_in_pool=pool,
        kv_pool_bytes=pool * per_block, weight_bytes=weights,
        filled_bytes_at_most=weights + most * per_block)


def serve_reference(cfg, width, control=False):
    """The plain reference over ``prompt + served tokens``, pass by pass and
    layer by layer (``control``: False, ``"int8"``, ``"fp8"`` or a planted
    fault of ``reference.FAULTS``)."""
    return reference.ServeReference(cfg, width, control=control)

"""Family ``jamba``: all the harness knows of the Jamba hybrid decoder
(``benchmark/README.md``, "A family"): the program's model built from the
configuration's own keys (``apex_tpu.models.pattern_decoder``), the seed's
weights handed to it as the reference drew them, the plain reference and its
controls (``jamba_reference.py``), the operations and bytes the algorithm
needs (``jamba_counts.py``), and what a deployment holds on the chip. Serving
only: the family has no trainer.
"""

from benchmark.families import jamba_counts as counts  # noqa: F401
from benchmark.families import jamba_reference as reference
from benchmark.families.jamba_reference import (  # noqa: F401
    make_weights, seed_key)


def vocab(cfg):
    """The traffic draws its token ids below this: the whole vocabulary."""
    return cfg["vocab_size"]


def model(cfg):
    """The program's model at the configuration's sizes."""
    from apex_tpu.models.pattern_decoder import (PatternDecoder,
                                                 PatternDecoderConfig)
    E, N, R, d = reference.sizes(cfg)
    return PatternDecoder(PatternDecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=reference.layer_types(cfg),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"], head_dim=d,
        layer_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        block="sequential", norm="rmsnorm", tie_embeddings=True,
        intermediate_size=cfg["intermediate_size"],
        mamba1_inner=E, mamba1_dt_rank=R, mamba_state=N,
        mamba_conv=cfg["mamba_d_conv"],
        mamba_chunk=cfg.get("scan_chunk", 128)))


def serve_engine(cfg, eng, seed):
    """The paged engine over the pattern decoder: the reference's bfloat16
    tensors are the program's parameters as they are (same names, same
    layout), so the weights exist ONCE on the chip; a block pool for the
    attention layers, one state row a slot for the Mamba layers, a few
    prefill buckets."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serving import ServingEngine

    # the model first: a program that cannot build it (one from before the
    # layer kinds this family needs) fails here, before any weight is drawn
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
    """The weights as stored, the KV blocks the traffic filled at the most
    and the state rows of the slots it had live at the most."""
    block = eng["block_size"]
    most_blocks = most_slots = 0
    for _, _, step in decode_calls:
        most_blocks = max(most_blocks,
                          counts.blocks_filled(step["contexts"], block))
        most_slots = max(most_slots, len(step["contexts"]))
    per_block = counts.block_bytes(cfg, block)
    per_slot = counts.state_bytes_per_slot(cfg)
    weights = counts.weight_bytes(cfg)
    pool = sum(eng["num_blocks"].values())
    return dict(
        kv_blocks_filled_at_most=most_blocks, kv_blocks_in_pool=pool,
        kv_pool_bytes=pool * per_block,
        state_slots_live_at_most=most_slots,
        state_bytes=eng["max_seqs"] * per_slot, weight_bytes=weights,
        filled_bytes_at_most=weights + most_blocks * per_block
        + most_slots * per_slot)


def serve_reference(cfg, width, control=False):
    """The plain reference over ``prompt + served tokens``, layer by layer
    (``control``: False, ``"int8"``, ``"fp8"`` or a planted fault of
    ``reference.FAULTS``)."""
    return reference.ServeReference(cfg, width, control=control)

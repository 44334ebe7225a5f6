"""Family ``nemotron_h``: all the harness knows of the Nemotron-H hybrid
decoder (``benchmark/README.md``, "A family"): the program's model built
from the configuration's own keys (``apex_tpu.models.pattern_decoder``),
the seed's weights handed to it as the reference drew them, the plain
reference and its controls (``nemotron_h_reference.py``), the operations
and bytes the algorithm needs (``nemotron_h_counts.py``), and what a
deployment holds on the chip. Serving only: the family has no trainer.
"""

from benchmark.families import nemotron_h_counts as counts  # noqa: F401
from benchmark.families import nemotron_h_reference as reference
from benchmark.families.nemotron_h_reference import (  # noqa: F401
    make_weights, seed_key)


def vocab(cfg):
    """The traffic draws its token ids below this: the chip's rows of the
    embedding and of the head."""
    return cfg["vocab_size"]


def model(cfg):
    """The program's model at the configuration's sizes."""
    from apex_tpu.models.pattern_decoder import (PatternDecoder,
                                                 PatternDecoderConfig)
    return PatternDecoder(PatternDecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=reference.layer_types(cfg),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        expert_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=tuple(reference.held_experts(cfg)),
        num_shared_experts=0, sliding_window=0,
        layer_norm_eps=cfg["layer_norm_epsilon"],
        max_position_embeddings=cfg["max_position_embeddings"],
        block="prenorm", norm="rmsnorm", tie_embeddings=False,
        expert_activation="relu2", router_bias=True,
        routed_scaling=float(cfg["routed_scaling_factor"]),
        latent_size=cfg["moe_latent_size"],
        shared_expert_size=cfg["moe_shared_expert_intermediate_size"],
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], mamba_groups=cfg["n_groups"],
        mamba_state=cfg["ssm_state_size"], mamba_conv=cfg["conv_kernel"],
        mamba_chunk=cfg["chunk_size"]))


def serve_engine(cfg, eng, seed):
    """The paged engine over the pattern decoder: the reference's bfloat16
    tensors are the program's parameters as they are (same names, same
    layout), so the weights exist ONCE on the chip; a block pool for the
    ``*`` layers, one state row a slot for the ``M`` layers, a few prefill
    buckets."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serving import PagedServingEngine

    # the model first: a program that cannot build it (one from before the
    # layer kinds this family needs) fails here, before any weight is drawn
    program = model(cfg)
    lo, hi = seed_key(seed)
    params = jax.block_until_ready(
        jax.jit(lambda lo, hi: make_weights(cfg, lo, hi))(lo, hi))
    return PagedServingEngine(
        program, params, max_seqs=eng["max_seqs"], max_len=eng["max_len"],
        prefill_len=eng["prefill_buckets"],
        cache_dtype=jnp.dtype(eng["cache_dtype"]),
        speculate_k=eng["speculate_k"], block_size=eng["block_size"],
        num_blocks=eng["num_blocks"])


def step_facts(engine, sched):
    """The cached positions of each active slot, read before the call —
    and, into the facts of the call BEFORE this one, what the program
    counted in it (``expert_stats``: it came back with that call's
    tokens). The counts take a call's facts as keyword arguments."""
    last = getattr(engine, "_bench_last_facts", None)
    if last is not None and engine.last_stats is not None:
        last["expert_stats"] = engine.last_stats.tolist()
    facts = {"contexts": [st.position for st in sched.active.values()]}
    engine._bench_last_facts = facts
    return facts


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

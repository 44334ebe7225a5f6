"""Family ``mistral4``: all the harness knows of Mistral Small 4 (DeepSeek-V3's
layer: latent attention, then a top-k sigmoid expert layer with a selection
bias and one shared expert) (``benchmark/README.md``, "A family"): the
program's model built from the configuration's own keys
(``apex_tpu.models.pattern_decoder``), the seed's weights handed to it as
the reference drew them, the plain reference and its controls
(``mistral4_reference.py``), the operations and bytes the algorithm needs
(``mistral4_counts.py``), and what a deployment holds on the chip. Serving
only: the family has no trainer.
"""

from benchmark.families import mistral4_counts as counts  # noqa: F401
from benchmark.families import mistral4_reference as reference
from benchmark.families.mistral4_reference import (  # noqa: F401
    make_weights, seed_key)


def vocab(cfg):
    """The traffic draws its token ids below this: the chip's rows of the
    embedding and of the head."""
    return cfg["vocab_size"]


def model(cfg):
    """The program's model at the configuration's sizes."""
    from apex_tpu.models.pattern_decoder import (PatternDecoder,
                                                 PatternDecoderConfig)
    p = reference.rope(cfg)
    if p["mscale"] != p["mscale_all_dim"]:
        raise ValueError("YaRN's rotation scaled by mscale / mscale_all_dim "
                         "!= 1 is not served")
    heads = cfg["num_attention_heads"]
    return PatternDecoder(PatternDecoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=reference.layer_types(cfg),
        num_attention_heads=heads, num_key_value_heads=heads,
        head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        expert_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=tuple(reference.held_experts(cfg)),
        num_shared_experts=0,
        shared_expert_size=cfg["moe_intermediate_size"]
        * cfg["n_shared_experts"],
        router_bias=True, routed_scaling=float(cfg["routed_scaling_factor"]),
        rope_theta=float(p["rope_theta"]), layer_norm_eps=cfg["rms_norm_eps"],
        max_position_embeddings=cfg["max_position_embeddings"],
        block="sequential", norm="rmsnorm", tie_embeddings=False,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_factor=float(p["factor"]),
        rope_original=p["original_max_position_embeddings"],
        rope_beta_fast=float(p["beta_fast"]),
        rope_beta_slow=float(p["beta_slow"]),
        rope_mscale_all_dim=float(p["mscale_all_dim"]),
        llama4_scaling_beta=float(p.get("llama_4_scaling_beta", 0.0))))


def serve_engine(cfg, eng, seed):
    """The paged engine over the pattern decoder: the reference's bfloat16
    tensors are the program's parameters as they are (same names, same
    layout), so the weights exist ONCE on the chip; a pool of latent rows,
    a few prefill buckets."""
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
    """The cached positions of each active slot, read before the call —
    and, into the facts of the call BEFORE this one, what the program
    counted in it (``expert_stats``: it came back with that call's
    tokens) and the latent positions its decode kernel read
    (``latent_positions``: the engine's count grew by them). The counts
    take a call's facts as keyword arguments."""
    last = getattr(engine, "_bench_last_facts", None)
    read = getattr(engine, "latent_positions_read", None)
    if last is not None:
        if engine.last_stats is not None:
            last["expert_stats"] = engine.last_stats.tolist()
        if read is not None:
            last["latent_positions"] = read - last.pop("_read_before")
    facts = {"contexts": [st.position for st in sched.active.values()]}
    if read is not None:
        facts["_read_before"] = read
    engine._bench_last_facts = facts
    return facts


def held_bytes(cfg, eng, decode_calls):
    """The weights as stored and the latent blocks the traffic filled at
    the most."""
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
    """The plain reference over ``prompt + served tokens``, layer by layer
    (``control``: False, ``"int8"``, ``"fp8"`` or a planted fault of
    ``reference.FAULTS``)."""
    return reference.ServeReference(cfg, width, control=control)

"""Family ``cohere2_moe``: all the harness knows of the Cohere2 sparse
decoder (``benchmark/README.md``, "A family"): the program's model built
from the configuration's own keys (``apex_tpu.models.pattern_decoder``),
the seed's weights handed to it as the reference drew them, the plain
reference and its controls (``cohere2_moe_reference.py``), the operations
and bytes the algorithm needs (``cohere2_moe_counts.py``), and what a
deployment holds on the chip. Serving only: the family has no trainer.
"""

from benchmark.families import cohere2_moe_counts as counts  # noqa: F401
from benchmark.families import cohere2_moe_reference as reference
from benchmark.families.cohere2_moe_reference import (  # noqa: F401
    make_weights, seed_key)


def vocab(cfg):
    """The traffic draws its token ids below this: the chip's rows of the
    embedding."""
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
        head_dim=cfg["head_dim"], expert_size=cfg["intermediate_size"],
        num_experts=cfg["router_width"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=tuple(cfg["held_experts"]),
        num_shared_experts=cfg["num_shared_experts"],
        sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]),
        layer_norm_eps=cfg["layer_norm_eps"],
        logit_scale=float(cfg["logit_scale"]),
        max_position_embeddings=cfg["max_position_embeddings"]))


def serve_engine(cfg, eng, seed):
    """The paged engine over the pattern decoder: the reference's tensors
    are the program's parameters as they are (same names, same layout,
    bfloat16), pools and block tables by layer kind, a few prefill
    buckets."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.serving import PagedServingEngine

    lo, hi = seed_key(seed)
    params = jax.block_until_ready(
        jax.jit(lambda lo, hi: make_weights(cfg, lo, hi))(lo, hi))
    return PagedServingEngine(
        model(cfg), params, max_seqs=eng["max_seqs"], max_len=eng["max_len"],
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
    """The weights as stored and the KV blocks the traffic filled at the
    most, by layer kind."""
    block = eng["block_size"]
    most = {kind: 0 for kind in eng["num_blocks"]}
    for _, _, step in decode_calls:
        for kind, n in counts.blocks_filled(cfg, step["contexts"],
                                            block).items():
            most[kind] = max(most[kind], n)
    per_block = {kind: counts.block_bytes(cfg, kind, block)
                 for kind in most}
    weights = counts.weight_bytes(cfg)
    return dict(
        kv_blocks_filled_at_most=most, kv_blocks_in_pool=eng["num_blocks"],
        kv_pool_bytes=sum(eng["num_blocks"][k] * per_block[k] for k in most),
        weight_bytes=weights,
        filled_bytes_at_most=weights + sum(most[k] * per_block[k]
                                           for k in most))


def serve_reference(cfg, width, control=False):
    """The plain reference over ``prompt + served tokens``, layer by layer
    (``control``: False, ``"int8"``, ``"fp8"`` or a planted fault of
    ``reference.FAULTS``)."""
    return reference.ServeReference(cfg, width, control=control)

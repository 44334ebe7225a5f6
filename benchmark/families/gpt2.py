"""Family ``gpt2``: everything the kinds, the readers and the tools need to
know of the GPT-2 block stack, behind one module that ``run.py`` finds by
the configuration's ``"family"``.

What a family is (``benchmark/README.md``, "A family"): the program's
model built from the configuration's own keys, the weights drawn from the
seed and handed to the program in its layout, the plain reference and its
control, the operations and bytes the algorithm needs (``counts``), and
what a deployment holds on the chip. ``benchmark/reference.py`` and
``benchmark/counts.py`` are this family's reference and counts.
"""

from benchmark import counts, reference  # noqa: F401  (family.counts.<name>)
from benchmark.reference import make_weights, seed_key  # noqa: F401


def vocab(cfg):
    """The traffic draws its token ids below this."""
    return cfg["vocab_size"]


# -- weights: the reference's layout <-> the program's ------------------------

def to_engine(w):
    """The reference's tensors as ``GPTModel.init``'s pytree: a tensor
    axis of size 1 added, no number changed."""
    def lin(name):
        return {"weight": w[f"{name}_w"][:, None],
                "bias": w[f"{name}_b"][:, None]}

    def ln(name):
        return {"weight": w[f"{name}_w"], "bias": w[f"{name}_b"]}

    return {"embedding": {"word": {"weight": w["wte"][None]},
                          "position": w["wpe"]},
            "layers": {"ln1": ln("ln1"), "qkv": lin("qkv"),
                       "proj": lin("proj"), "ln2": ln("ln2"),
                       "fc1": lin("fc1"), "fc2": lin("fc2")},
            "final_ln": {"weight": w["lnf_w"], "bias": w["lnf_b"]}}


def to_trainer(w):
    """The reference's tensors as the trainer's ``(stage_stack, shared)``:
    pipeline and tensor axes of size 1 added, no number changed."""
    def lin(name):
        return {"weight": w[f"{name}_w"][None, :, None],
                "bias": w[f"{name}_b"][None, :, None]}

    def ln(name):
        return {"weight": w[f"{name}_w"][None], "bias": w[f"{name}_b"][None]}

    stage_stack = {"ln1": ln("ln1"), "qkv": lin("qkv"), "proj": lin("proj"),
                   "ln2": ln("ln2"), "fc1": lin("fc1"), "fc2": lin("fc2")}
    shared = {"embedding": {"word": {"weight": w["wte"][None]},
                            "position": w["wpe"]},
              "final_ln": {"weight": w["lnf_w"], "bias": w["lnf_b"]}}
    return stage_stack, shared


def from_trainer(stage_stack, shared):
    out = {"wte": shared["embedding"]["word"]["weight"][0],
           "wpe": shared["embedding"]["position"],
           "lnf_w": shared["final_ln"]["weight"],
           "lnf_b": shared["final_ln"]["bias"]}
    for name in ("ln1", "ln2"):
        out[f"{name}_w"] = stage_stack[name]["weight"][0]
        out[f"{name}_b"] = stage_stack[name]["bias"][0]
    for name in ("qkv", "proj", "fc1", "fc2"):
        out[f"{name}_w"] = stage_stack[name]["weight"][0, :, 0]
        out[f"{name}_b"] = stage_stack[name]["bias"][0, :, 0]
    return out


# -- serving -------------------------------------------------------------------

def serve_engine(cfg, eng, seed):
    """The engine the serve kind puts under its scheduler: the model at the
    configuration's sizes, the seed's weights, and the keys of the cell's
    ``engine`` block this family's engine takes."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving import PagedServingEngine

    model = GPTModel(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
        num_layers=cfg["n_layer"], num_attention_heads=cfg["n_head"],
        max_position_embeddings=cfg["n_positions"],
        ffn_hidden_size=cfg.get("n_inner"),
        layernorm_epsilon=cfg["layer_norm_epsilon"]))
    lo, hi = seed_key(seed)
    params = jax.block_until_ready(jax.jit(
        lambda lo, hi: to_engine(make_weights(cfg, lo, hi))
    )(lo, hi))
    return PagedServingEngine(
        model, params, max_seqs=eng["max_seqs"], max_len=eng["max_len"],
        prefill_len=eng["prefill_len"],
        cache_dtype=jnp.dtype(eng["cache_dtype"]),
        speculate_k=eng["speculate_k"], block_size=eng["block_size"],
        num_blocks=eng["num_blocks"])


def step_facts(engine, sched):
    """What the serve kind keeps with each decode and prefill call, read
    before the call: the cached positions of each active slot. The counts
    take a call's facts as keyword arguments."""
    return {"contexts": [st.position for st in sched.active.values()]}


def held_bytes(cfg, eng, decode_calls):
    """How much of the KV pool the traffic fills: the most blocks that
    held a cached position at any decode step (a slot with ``c`` positions
    holds ``ceil(c / block_size)``), and the bytes a deployment really
    holds at that instant: the stored weights and those blocks."""
    import jax.numpy as jnp

    block = eng["block_size"]
    most = max((sum(-(-c // block) for c in step["contexts"])
                for _, _, step in decode_calls), default=0)
    block_bytes = (2 * cfg["n_layer"] * cfg["n_embd"] * block
                   * jnp.dtype(eng["cache_dtype"]).itemsize)
    weights = 4 * counts.n_params(cfg)        # stored float32
    return dict(kv_blocks_filled_at_most=most,
                kv_blocks_in_pool=eng["num_blocks"],
                kv_pool_bytes=eng["num_blocks"] * block_bytes,
                filled_bytes_at_most=weights + most * block_bytes)


def serve_reference(cfg, width, control=False):
    """The plain reference over ``prompt + served tokens`` up to ``width``
    positions (``control``: False, ``"int8"`` or ``"fp8"``); its
    ``gaps(make_weights(...), prompts, streams)`` scores the served
    tokens."""
    return reference.ServeReference(cfg, width, control=control)


# -- training ------------------------------------------------------------------

def trainer(cfg, job, devices):
    """The program's trainer for the cell's ``job`` on ``devices`` and the
    mesh it runs on."""
    from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                                 ParallelConfig, TrainConfig)
    from apex_tpu.training import GPTHybridTrainer

    o = job["optimizer"]
    tc = TrainConfig(
        model=ModelConfig(
            name="gpt", vocab_size=cfg["vocab_size"],
            hidden_size=cfg["n_embd"], num_layers=cfg["n_layer"],
            num_attention_heads=cfg["n_head"],
            max_position_embeddings=cfg["n_positions"],
            ffn_hidden_size=cfg.get("n_inner")),
        parallel=ParallelConfig(tensor_model_parallel_size=1),
        batch=BatchConfig(
            global_batch_size=(job["microbatches"] * job["micro_batch"]
                               * job["dp"]),
            micro_batch_size=job["micro_batch"]),
        optimizer=OptimizerConfig(
            name=o["name"], lr=o["lr"], weight_decay=o["weight_decay"],
            betas=tuple(o["betas"]), eps=o["eps"], zero=job["zero"]),
        opt_level=job["opt_level"], half_dtype=job["half_dtype"])
    mesh = tc.initialize_mesh(devices=devices)
    return GPTHybridTrainer(tc, mesh), mesh


def train_reference(cfg, job, **kw):
    """The plain reference that follows the trainer's first steps (``kw``:
    ``quant``, ``keep_share``, ``rows_per_block``, ``devices``)."""
    return reference.TrainReference(cfg, job, **kw)

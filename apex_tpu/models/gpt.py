"""Standalone GPT — the flagship model / "model zoo" fixture.

Reference: ``reference:apex/transformer/testing/standalone_gpt.py`` (1,524
LoC) — ``ParallelMLP`` (:236), ``ParallelAttention`` (:285),
``ParallelTransformerLayer`` (:577), ``ParallelTransformer`` (:713),
``Embedding`` (:1000), ``TransformerLanguageModel`` (:1150), ``GPTModel``
(:1440). Same architecture (pre-LN GPT-2 style, learned positions, tied
output embedding, vocab-parallel loss), rebuilt TPU-first:

- attention is the Pallas flash kernel (no seqlen-2048 fused-softmax cap);
- QKV/proj/MLP are Column/Row-parallel over the ``tensor`` axis with heads
  sharded tp-ways, exactly the reference's sharding;
- homogeneous layers are stacked and scanned (``lax.scan``) so compile time
  is O(1) in depth — the idiomatic XLA shape for deep stacks — with optional
  per-layer remat (the reference's activation checkpointing);
- everything is bf16 compute / fp32 params by default (amp O2 semantics).

Works single-chip (tp=1, no mesh needed), under ``shard_map`` for TP, and as
a pipeline ``stage_fn`` (see :meth:`GPTModel.stage_fn`).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.normalization import fused_layer_norm_affine
from apex_tpu.ops.dropout import dropout
from apex_tpu.ops.mlp import gelu_tanh
from apex_tpu.remat import RematPolicy, tag as _remat_tag
from apex_tpu.ops.flash_attention import (flash_attention,
                                          flash_attention_qkv, merge_heads,
                                          paged_decode_attention,
                                          paged_work_list, split_heads)
from apex_tpu.ops.xentropy import softmax_cross_entropy_loss
from apex_tpu.transformer import tensor_parallel as tp_mod
from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy)
from apex_tpu.transformer.tensor_parallel.layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    _local_shard, init_method_normal)
from apex_tpu.utils.vma import scan_stable_vma

__all__ = ["GPTConfig", "GPTModel"]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    """Sizes follow the Megatron arg names (``testing/arguments.py``)."""
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 1024
    ffn_hidden_size: Optional[int] = None  # default 4*hidden
    tensor_model_parallel_size: int = 1
    params_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.bfloat16
    init_method_std: float = 0.02
    layernorm_epsilon: float = 1e-5
    # Per-layer activation rematerialization. ``remat_policy`` is the
    # knob: None | "none" | "full" | "selective" | "offload" | a
    # remat.RematPolicy instance ("selective" keeps the registry-tagged
    # GEMM/flash outputs resident and recomputes only the cheap LN/gelu
    # tier — see apex_tpu/remat.py). ``remat: bool`` is the deprecated
    # pre-policy spelling, honored (True -> "full") when remat_policy is
    # None. ``remat_names``: custom save-list for the name-based modes.
    remat: bool = False
    remat_policy: Any = None
    remat_names: Optional[Tuple[str, ...]] = None
    use_flash: Optional[bool] = None  # None = auto by shape/backend
    # Megatron-LM sequence parallelism: norms/dropout/residuals run on
    # (b, s/tp, h) sequence shards; ColumnParallel inputs all-gather the
    # sequence, RowParallel outputs reduce-scatter back to shards
    sequence_parallel: bool = False
    # Ring-decompose the SP gather/reduce-scatter under their GEMMs
    # (tensor_parallel.collective_matmul) so the dependent TP collectives
    # overlap with compute in fwd AND bwd; requires sequence_parallel
    tp_comm_overlap: bool = False
    # Layer-stack scan unroll factor (lax.scan's ``unroll``): 1 = compact
    # while loop (O(1) compile in depth), num_layers/True = fully
    # unrolled. Unrolled programs are what XLA's cost_analysis can count
    # end to end (a while body is priced once regardless of trip count),
    # so scripts/attribute_step.py uses True to validate the pyprof
    # roofline against flops_budget; on TPU, small factors (2-4) can also
    # buy scheduling overlap across layer boundaries.
    layer_scan_unroll: Any = 1
    # Dropout (standalone_gpt.py attention/hidden dropout; 0.0 = off so
    # eval-style calls stay deterministic without threading an rng).
    # Semantics under TP follow the reference's RNG stream layout
    # (tensor_parallel/random.py:200-230): hidden+embedding dropout draw
    # from the caller's key (identical across TP ranks — the activations
    # are replicated), attention-probability dropout folds in the TP rank
    # (the heads are sharded, each rank's slice gets an independent mask).
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0

    @property
    def ffn(self) -> int:
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


class GPTModel:
    """Param-factory GPT. ``init(key)`` -> params pytree; ``__call__`` gives
    logits; ``loss`` gives the LM loss (vocab-parallel when tp>1)."""

    def __init__(self, config: GPTConfig):
        cfg = config
        if cfg.hidden_size % cfg.num_attention_heads:
            raise ValueError("hidden_size must divide num_attention_heads")
        if cfg.num_attention_heads % cfg.tensor_model_parallel_size:
            raise ValueError("heads must divide tp size")
        self.cfg = cfg
        # remat policy resolved ONCE (the deprecation warning for the
        # legacy bool fires here); models gate their checkpoint_name tags
        # on uses_names so none/full programs stay tag-free and
        # jaxpr-identical to the pre-policy ones
        policy = RematPolicy.resolve(
            cfg.remat_policy, legacy_bool=cfg.remat,
            owner=type(cfg).__name__)
        if cfg.remat_names is not None:
            if not policy.uses_names:
                raise ValueError(
                    "remat_names requires a name-based remat_policy "
                    "('selective' or 'offload'), got "
                    f"{policy.mode!r}")
            if policy.names is not None and policy.names != tuple(
                    cfg.remat_names):
                raise ValueError(
                    "conflicting save-lists: remat_policy carries "
                    f"names={policy.names!r} but remat_names="
                    f"{tuple(cfg.remat_names)!r}; set the list in one "
                    "place")
            policy = dataclasses.replace(
                policy, names=tuple(cfg.remat_names))
        self.remat_policy = policy
        self._tag = (_remat_tag if policy.uses_names
                     else (lambda x, name: x))
        tp = cfg.tensor_model_parallel_size
        init = init_method_normal(cfg.init_method_std)
        # output-layer init scaled by sqrt(2*layers) (standalone_gpt.py
        # scaled_init_method pattern)
        out_init = init_method_normal(
            cfg.init_method_std / math.sqrt(2.0 * cfg.num_layers))
        self.embedding = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, init_method=init,
            params_dtype=cfg.params_dtype, world_size=tp)
        if cfg.sequence_parallel and tp <= 1:
            raise ValueError("sequence_parallel requires tp > 1")
        if cfg.tp_comm_overlap and not cfg.sequence_parallel:
            raise ValueError(
                "tp_comm_overlap requires sequence_parallel=True: only the "
                "SP gather->GEMM / GEMM->reduce-scatter pairs are dependent "
                "collectives (plain-TP collectives already overlap)")
        sp = cfg.sequence_parallel
        ov = cfg.tp_comm_overlap
        self.qkv = ColumnParallelLinear(
            cfg.hidden_size, 3 * cfg.hidden_size, gather_output=False,
            init_method=init, params_dtype=cfg.params_dtype, world_size=tp,
            sequence_parallel=sp, seq_axis=1, tp_comm_overlap=ov)
        self.proj = RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size, input_is_parallel=True,
            init_method=out_init, params_dtype=cfg.params_dtype,
            world_size=tp, sequence_parallel=sp, seq_axis=1,
            tp_comm_overlap=ov)
        self.fc1 = ColumnParallelLinear(
            cfg.hidden_size, cfg.ffn, gather_output=False, init_method=init,
            params_dtype=cfg.params_dtype, world_size=tp,
            sequence_parallel=sp, seq_axis=1, tp_comm_overlap=ov)
        self.fc2 = RowParallelLinear(
            cfg.ffn, cfg.hidden_size, input_is_parallel=True,
            init_method=out_init, params_dtype=cfg.params_dtype,
            world_size=tp, sequence_parallel=sp, seq_axis=1,
            tp_comm_overlap=ov)

    # -- params -------------------------------------------------------------

    def _layer_init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        k = jax.random.split(key, 4)
        h = cfg.hidden_size
        return {
            "ln1": {"weight": jnp.ones(h, cfg.params_dtype),
                    "bias": jnp.zeros(h, cfg.params_dtype)},
            "qkv": self.qkv.init(k[0]),
            "proj": self.proj.init(k[1]),
            "ln2": {"weight": jnp.ones(h, cfg.params_dtype),
                    "bias": jnp.zeros(h, cfg.params_dtype)},
            "fc1": self.fc1.init(k[2]),
            "fc2": self.fc2.init(k[3]),
        }

    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        kw, kp, kl = jax.random.split(key, 3)
        layer_keys = jax.random.split(kl, cfg.num_layers)
        layers = jax.vmap(self._layer_init)(layer_keys)
        return {
            "embedding": {
                "word": self.embedding.init(kw),
                "position": init_method_normal(cfg.init_method_std)(
                    kp, (cfg.max_position_embeddings, cfg.hidden_size)
                ).astype(cfg.params_dtype),
            },
            "layers": layers,  # leaves stacked (num_layers, ...)
            "final_ln": {"weight": jnp.ones(cfg.hidden_size, cfg.params_dtype),
                         "bias": jnp.zeros(cfg.hidden_size, cfg.params_dtype)},
        }

    def serving_params(self, params: dict) -> dict:
        """The image of ``params`` the KV-cached passes (:meth:`forward`
        with a cache, :meth:`verify_forward`) are meant to be handed:
        the four matrices of every layer already in
        ``cfg.compute_dtype``, so a serving program reads them as they
        lie and does not round 2 x their size on every run (the MXU
        takes them in that dtype whatever they are stored in). The tied
        head, which wants the word table in the compute dtype, gets its
        own copy of it as ``["head"]["weight"]``; the word and position
        tables keep their dtype, because the lookup adds their rows in
        it and rounds the SUM. Both copies of the word table are
        ``(vocab, hidden)``, the shape the gather and the head's product
        take: the tensor axis (1 on this path) would cost the lookup a
        copy of the table into another layout every run.

        The biases and the norms' gains and biases stay as stored. They
        are a thousandth of the bytes, and the TPU compiler, which may
        keep excess precision, applies them unrounded where the code
        says ``.astype(x.dtype)``: stored in the compute dtype they
        moved every logit of gpt2-large on the chip (by up to 0.012; the
        matrices alone move no decode logit by a bit).

        ``params`` whose matrices are in the compute dtype already, and
        an image, come back as they are; a serving engine makes the
        image at build and at every swap. Not for training: the
        optimizer's master weights are ``params``.
        """
        self._require_cacheable()
        dtype = self.cfg.compute_dtype
        layers = {name: dict(leaves)
                  for name, leaves in params["layers"].items()}
        for name in ("qkv", "proj", "fc1", "fc2"):
            layers[name]["weight"] = layers[name]["weight"].astype(dtype)
        image = dict(params, layers=layers)
        word = params["embedding"]["word"]["weight"]
        if "head" not in params and word.dtype != dtype:
            image["embedding"] = dict(params["embedding"],
                                      word={"weight": word[0]})
            image["head"] = {"weight": word[0].astype(dtype)}
        return image

    def _word_rows(self, params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
        """Rows of the word table for the cached passes: a serving image
        stores it ``(vocab, hidden)`` (:meth:`serving_params`)."""
        word = params["embedding"]["word"]
        if word["weight"].ndim == 2:
            return jnp.take(word["weight"], tokens, axis=0)
        return self.embedding(word, tokens)

    def param_specs(self, params: dict):
        """``PartitionSpec`` tree for a :meth:`init` params pytree under
        the standard TP layout (vocab-sharded embedding, per-layer TP
        stacks on axis 1, replicated norms/positions) — the specs every
        ``shard_map`` over the whole model needs; keep call sites on this
        helper instead of hand-copying the literal."""
        from jax.sharding import PartitionSpec as P
        return {
            "embedding": {"word": {"weight": P("tensor")},
                          "position": P()},
            "final_ln": {"weight": P(), "bias": P()},
            "layers": jax.tree_util.tree_map(
                lambda p: P(None, "tensor") if p.ndim >= 3 else P(),
                params["layers"]),
        }

    # -- blocks -------------------------------------------------------------

    def _ln(self, p: dict, x: jnp.ndarray) -> jnp.ndarray:
        # mixed-dtype rule: bf16 activations, fp32 ln params -> bf16 out.
        # The named_scope is a pyprof attribution region
        # (scripts/check_annotations.py contract).
        with jax.named_scope("gpt_ln"):
            out = fused_layer_norm_affine(
                x, p["weight"].astype(x.dtype), p["bias"].astype(x.dtype),
                self.cfg.hidden_size, eps=self.cfg.layernorm_epsilon)
        # dropped by the selective policy: recomputing an LN is one fused
        # elementwise pass — the cheap tier selective remat exists to shed
        return self._tag(out, "ln_out")

    @jax.named_scope("gpt_attention")
    def _attention(self, lp: dict, x: jnp.ndarray,
                   attn_seed=None, collect_kv: bool = False):
        cfg = self.cfg
        local_heads = cfg.num_attention_heads // cfg.tensor_model_parallel_size
        qkv, _ = self.qkv(lp["qkv"], x)  # (b, s_full, 3*h/tp) — under SP
        # the ColumnParallel input gather restores the full sequence here
        qkv = self._tag(qkv, "qkv_out")
        rate = cfg.attention_dropout if attn_seed is not None else 0.0
        kw = dict(causal=True, use_pallas=cfg.use_flash, dropout_rate=rate,
                  dropout_seed=attn_seed,
                  checkpoint_names=self.remat_policy.uses_names)
        if collect_kv:
            q, k, v = split_heads(qkv, local_heads)   # (b, nh, s, d)
            ctx = merge_heads(flash_attention(q, k, v, **kw))
        else:
            # the QKV activation as it lies: heads of 64 in pairs take the
            # packed kernels, any other shape the split heads
            ctx = flash_attention_qkv(qkv, local_heads, **kw)
        out, _ = self.proj(lp["proj"], ctx)
        out = self._tag(out, "attn_proj_out")
        if collect_kv:
            # prefill: the serving cache wants this layer's K/V alongside
            return out, (k, v)
        return out

    @jax.named_scope("gpt_mlp")
    def _mlp(self, lp: dict, x: jnp.ndarray) -> jnp.ndarray:
        h, _ = self.fc1(lp["fc1"], x)
        # tagged PRE-gelu: saving the GEMM output costs the same bytes and
        # leaves only the elementwise gelu to recompute for fc2's dW; the
        # gelu's own backward needs that tensor alone (ops.mlp.gelu_tanh)
        h = self._tag(h, "mlp_fc1_out")
        h = gelu_tanh(h)
        out, _ = self.fc2(lp["fc2"], h)
        return self._tag(out, "mlp_fc2_out")

    def _layer(self, lp: dict, x: jnp.ndarray, lrng=None,
               collect_kv: bool = False):
        cfg = self.cfg
        attn_seed = lrng["attn_seed"] if lrng is not None else None
        a = self._attention(lp, self._ln(lp["ln1"], x), attn_seed,
                            collect_kv=collect_kv)
        if collect_kv:
            a, kv = a
        if lrng is not None:
            a = dropout(a, cfg.hidden_dropout, lrng["h1"])
        x = x + a
        m = self._mlp(lp, self._ln(lp["ln2"], x))
        if lrng is not None:
            m = dropout(m, cfg.hidden_dropout, lrng["h2"])
        x = x + m
        return (x, kv) if collect_kv else x

    def _layer_rngs(self, dropout_rng: jax.Array) -> dict:
        """Per-layer dropout randomness, stacked (num_layers, ...) for the
        scan: attention seeds from the TP-rank-folded stream, hidden keys
        from the caller's (TP-replicated) stream."""
        cfg = self.cfg
        attn_key = dropout_rng
        if cfg.tensor_model_parallel_size > 1:
            attn_key = jax.random.fold_in(
                attn_key, jax.lax.axis_index(TENSOR_AXIS) + 1)
        seeds = jax.random.randint(
            jax.random.fold_in(attn_key, 1), (cfg.num_layers,), 0,
            2 ** 31 - 1)
        hidden_key = jax.random.fold_in(dropout_rng, 2)
        if cfg.sequence_parallel:
            # SP: hidden dropout acts on per-rank sequence shards, so each
            # rank needs an independent stream (Megatron SP RNG semantics)
            hidden_key = jax.random.fold_in(
                hidden_key, jax.lax.axis_index(TENSOR_AXIS) + 1)
        hkeys = jax.random.split(hidden_key, 2 * cfg.num_layers)
        hkeys = hkeys.reshape(cfg.num_layers, 2, *hkeys.shape[1:])
        return {"attn_seed": seeds, "h1": hkeys[:, 0], "h2": hkeys[:, 1]}

    # -- forward ------------------------------------------------------------

    @jax.named_scope("gpt_embed")
    def embed(self, params: dict, tokens: jnp.ndarray,
              dropout_rng: Optional[jax.Array] = None) -> jnp.ndarray:
        cfg = self.cfg
        h = self._word_rows(params, tokens)
        pos = params["embedding"]["position"][: tokens.shape[1]]
        h = (h + pos).astype(cfg.compute_dtype)
        if cfg.sequence_parallel:
            from apex_tpu.transformer.context_parallel import (
                scatter_to_sequence_parallel_region)
            h = scatter_to_sequence_parallel_region(h, TENSOR_AXIS,
                                                    seq_axis=1)
        if dropout_rng is not None:
            # embedding dropout at the hidden rate (standalone_gpt
            # Embedding); under SP the rate applies to this rank's shard
            # with a rank-folded key (Megatron's SP RNG stream)
            key = jax.random.fold_in(dropout_rng, 3)
            if cfg.sequence_parallel:
                key = jax.random.fold_in(
                    key, jax.lax.axis_index(TENSOR_AXIS) + 1)
            h = dropout(h, cfg.hidden_dropout, key)
        return h

    def tp_overlap_fwd_bytes(self, shard_shape: Tuple[int, ...]) -> int:
        """Per-rank forward-ring ppermute bytes for ONE pass through the
        layer stack on a ``(b, s/tp, h)`` activation shard — the
        ``tp/collective_bytes`` accounting (a trace-time constant). The
        backward rings move the same chunk counts with fp32 payloads
        (dX/dY cotangents), so train-step traffic is this plus the
        fp32-scaled mirror."""
        cfg = self.cfg
        tp = cfg.tensor_model_parallel_size
        shard = 1
        for d in shard_shape:
            shard *= d
        col_bytes = shard * jnp.dtype(cfg.compute_dtype).itemsize
        row_bytes = shard * 4  # the traveling partial-sum acc is fp32
        # two Column rings (qkv, fc1) + two Row rings (proj, fc2) per layer
        return cfg.num_layers * (tp - 1) * (2 * col_bytes + 2 * row_bytes)

    def record_tp_overlap(self, shard_shape: Tuple[int, ...],
                          passes: int = 1) -> None:
        """``tp/*`` telemetry for the ring-decomposed SP collectives — the
        single recording site, called at the step-trace level (outside the
        layer scan / custom_vjp) because a record inside the scanned rings
        would capture one body *trace* instead of ``num_layers``
        *executions*. ``passes``: layer-stack passes per step (microbatch
        count under the pipelined trainer)."""
        from apex_tpu.observability import ingraph
        if not ingraph.recording():
            return
        ingraph.record("tp/overlap_chunks",
                       float(self.cfg.tensor_model_parallel_size),
                       reduce="mean")
        ingraph.record("tp/collective_bytes",
                       float(passes * self.tp_overlap_fwd_bytes(
                           shard_shape)), reduce="sum")

    def transform(self, params: dict, x: jnp.ndarray,
                  dropout_rng: Optional[jax.Array] = None) -> jnp.ndarray:
        """Run the layer stack (scan) + final LN. ``dropout_rng`` enables
        train-mode dropout (None = eval/deterministic)."""
        cfg = self.cfg
        if cfg.tp_comm_overlap:
            self.record_tp_overlap(x.shape)
        layer_fn = self.remat_policy.wrap(self._layer)
        use_dropout = dropout_rng is not None and (
            cfg.hidden_dropout > 0.0 or cfg.attention_dropout > 0.0)

        if use_dropout:
            xs = (params["layers"], self._layer_rngs(dropout_rng))

            def body(x, lp_rng):
                lp, lrng = lp_rng
                return layer_fn(lp, x, lrng), None
        else:
            xs = params["layers"]

            def body(x, lp):
                return layer_fn(lp, x), None

        x, _ = scan_stable_vma(body, x, xs,
                               unroll=cfg.layer_scan_unroll)
        x = self._ln(params["final_ln"], x)
        if cfg.sequence_parallel:
            from apex_tpu.transformer.context_parallel import (
                gather_from_sequence_parallel_region)
            x = gather_from_sequence_parallel_region(x, TENSOR_AXIS,
                                                     seq_axis=1,
                                                     invariant=True)
        return x

    @jax.named_scope("gpt_head_loss")
    def logits(self, params: dict, x: jnp.ndarray) -> jnp.ndarray:
        """Tied output embedding (standalone_gpt.py parallel_lm_logits):
        returns vocab-parallel logits (local shard) when tp>1."""
        if "head" in params:
            # a serving image brings the head's own (vocab, hidden) copy of
            # the word table in the compute dtype (serving_params); a
            # trainer's tree never does
            w = params["head"]["weight"]
        else:
            w = _local_shard(params["embedding"]["word"]["weight"],
                             self.cfg.tensor_model_parallel_size)
            if self.cfg.tensor_model_parallel_size == 1:
                from apex_tpu.utils.vma import restore_invariant
                from apex_tpu.transformer.parallel_state import TENSOR_AXIS
                w = restore_invariant(w, TENSOR_AXIS)
        return jax.lax.dot_general(
            x, w.astype(x.dtype), (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    def __call__(self, params: dict, tokens: jnp.ndarray,
                 dropout_rng: Optional[jax.Array] = None) -> jnp.ndarray:
        return self.logits(params, self.transform(
            params, self.embed(params, tokens, dropout_rng), dropout_rng))

    def loss(self, params: dict, tokens: jnp.ndarray,
             targets: jnp.ndarray, loss_mask: Optional[jnp.ndarray] = None,
             dropout_rng: Optional[jax.Array] = None) -> jnp.ndarray:
        """LM loss; vocab-parallel CE over the tensor axis when tp>1
        (``standalone_gpt.py`` post_language_model_processing).
        ``dropout_rng`` enables train-mode dropout."""
        logits = self(params, tokens, dropout_rng)
        with jax.named_scope("gpt_head_loss"):
            if self.cfg.tensor_model_parallel_size > 1:
                per_tok = vocab_parallel_cross_entropy(logits, targets)
            else:
                per_tok = softmax_cross_entropy_loss(
                    logits.reshape(-1, logits.shape[-1]),
                    targets.reshape(-1),
                    padding_idx=None, half_to_float=True
                ).reshape(targets.shape)
            if loss_mask is not None:
                return jnp.sum(per_tok * loss_mask) / jnp.maximum(
                    jnp.sum(loss_mask), 1.0)
            return jnp.mean(per_tok)

    # -- serving: KV-cached prefill/decode ----------------------------------

    def _require_cacheable(self):
        cfg = self.cfg
        if cfg.tensor_model_parallel_size != 1 or cfg.sequence_parallel:
            raise NotImplementedError(
                "the KV-cached serving path runs tp=1 (serve-mesh "
                "resharding is ROADMAP item 3); got tp="
                f"{cfg.tensor_model_parallel_size}, sequence_parallel="
                f"{cfg.sequence_parallel}")

    def forward(self, params: dict, tokens: jnp.ndarray,
                dropout_rng: Optional[jax.Array] = None,
                kv_cache=None, prompt_len=None,
                last_logit_only: bool = False,
                block_row: Optional[jnp.ndarray] = None,
                block_tables: Optional[jnp.ndarray] = None,
                lengths: Optional[jnp.ndarray] = None,
                append_block_ids: Optional[jnp.ndarray] = None,
                append_offsets: Optional[jnp.ndarray] = None,
                cow_src: Optional[jnp.ndarray] = None,
                cow_dst: Optional[jnp.ndarray] = None,
                mean_context: Optional[float] = None):
        """The cache-threading entry point (docs/SERVING.md).

        Without ``kv_cache`` this is :meth:`__call__`. With a
        :class:`~apex_tpu.serving.cache.PagedKVCache` it runs one of two
        legs against the block pool, picked by ``block_row``:

        - **prefill** (``block_row`` given): ``tokens`` is ``(1, P)``,
          ``P`` a multiple of the pool's block size — the ordinary causal
          forward (same flash path, same layer scan as training) that
          ALSO collects every layer's K/V and writes them into the pool
          blocks named by ``block_row`` (``(P // block_size,)`` int32,
          null-padded). ``prompt_len`` (default ``P``; right-pad shorter
          prompts) is the cursor the caller keeps. Returns ``(logits (1,
          P, vocab), new_cache)``.
        - **decode** (no ``block_row``): ``tokens`` is ``(max_seqs, 1)`` —
          one token per slot, every slot stepping together under a fixed
          shape. It first resolves any copy-on-write pairs
          (``cow_src``/``cow_dst``, null pairs no-op), reads each slot's
          context through ``block_tables``/``lengths`` with the bounded
          paged kernel — HBM per step is O(actual context), not
          O(max_len) — and appends the new token at
          ``append_block_ids``/``append_offsets`` (host-computed; null
          entries drop the write, which is how an inactive slot keeps a
          frozen cursor). ``lengths`` also indexes the position
          embedding. ``mean_context`` only prices the kernel's
          CostEstimate for pyprof. Returns ``(logits (max_seqs, vocab),
          new_cache)``.

        ``last_logit_only`` (prefill only): project the vocab head for
        JUST the position ``prompt_len - 1`` — logits come back
        ``(1, 1, vocab)``. The full-prompt head is the largest matmul in
        a prefill and a serving admission samples exactly one row of it;
        the serving engine always sets this (parity tests use the
        default full logits).

        Both legs are inference-mode (no dropout) and are meant to be
        AOT-compiled with the cache donated — see
        :class:`apex_tpu.serving.engine.ServingEngine`.
        """
        if kv_cache is None:
            return self(params, tokens, dropout_rng)
        self._require_cacheable()
        if block_row is not None:
            return self._paged_prefill_forward(
                params, tokens, kv_cache, block_row, prompt_len,
                last_logit_only)
        return self._paged_decode_forward(
            params, tokens, kv_cache, block_tables, lengths,
            append_block_ids, append_offsets, cow_src, cow_dst,
            mean_context)

    def _paged_decode_layer(self, lp: dict, x: jnp.ndarray, cache, layer,
                            block_tables: jnp.ndarray,
                            lengths: jnp.ndarray, block_ids: jnp.ndarray,
                            offsets: jnp.ndarray,
                            mean_context: Optional[float], work):
        """One layer of the decode step: ``x`` is ``(S, 1, hidden)``, one
        token per slot. The context comes through each slot's block
        table, so only ~ceil(cursor/block_size) pool blocks are streamed
        per slot (``work``, the step's walk of them). The
        kernel reads layer ``layer`` of the stacked pool where it lies;
        the layer then writes its own new K/V row there (after the read:
        the current token reaches attention through the merge)."""
        cfg = self.cfg
        h = self._ln(lp["ln1"], x)
        with jax.named_scope("gpt_attention"):
            qkv, _ = self.qkv(lp["qkv"], h)       # (S, 1, 3*hidden)
            S = qkv.shape[0]
            qkv = qkv.reshape(S, cfg.num_attention_heads, 3 * cfg.head_dim)
            q, k_new, v_new = jnp.split(qkv, 3, axis=-1)   # (S, H, D)
            ctx = paged_decode_attention(
                q, cache.k, cache.v, layer, block_tables, lengths,
                k_new=k_new, v_new=v_new, k_scale=cache.k_scale,
                v_scale=cache.v_scale, mean_context=mean_context,
                use_pallas=cfg.use_flash, work=work)
            cache = cache.append(layer, k_new, v_new, block_ids, offsets)
            out, _ = self.proj(lp["proj"], ctx.reshape(S, 1, -1))
        x = x + out
        x = x + self._mlp(lp, self._ln(lp["ln2"], x))
        return x, cache

    def _paged_prefill_forward(self, params, tokens, cache, block_row,
                               prompt_len, last_logit_only=False):
        cfg = self.cfg
        b, P = tokens.shape
        if b != 1:
            raise ValueError(f"prefill is per-request: tokens must be "
                             f"(1, P), got {tokens.shape}")
        if P % cache.block_size != 0:
            raise ValueError(f"paged prefill window {P} must be a "
                             f"multiple of block_size {cache.block_size}")
        if prompt_len is None:
            prompt_len = P
        elif isinstance(prompt_len, int):
            if not 0 < prompt_len <= P:
                raise ValueError(f"prompt_len {prompt_len} outside the "
                                 f"written window (1, {P}]")
        else:
            prompt_len = jnp.clip(jnp.asarray(prompt_len, jnp.int32), 1,
                                  P)
        x = self.embed(params, tokens)

        def body(x, lp):
            return self._layer(lp, x, collect_kv=True)

        x, (k_all, v_all) = scan_stable_vma(body, x, params["layers"],
                                            unroll=cfg.layer_scan_unroll)
        x = self._ln(params["final_ln"], x)
        if last_logit_only:
            x = jax.lax.dynamic_slice_in_dim(
                x, jnp.asarray(prompt_len, jnp.int32) - 1, 1, axis=1)
        logits = self.logits(params, x)
        # ys stacked (L, 1, H, P, D) -> (L, H, P, D) block-scattered
        # into the pool; null block_row entries absorb the padding
        cache = cache.write_prompt_blocks(k_all[:, 0], v_all[:, 0],
                                          jnp.asarray(block_row,
                                                      jnp.int32))
        return logits, cache

    def _paged_decode_forward(self, params, tokens, cache, block_tables,
                              lengths, block_ids, offsets, cow_src,
                              cow_dst, mean_context=None):
        cfg = self.cfg
        if tokens.ndim != 2 or tokens.shape[1] != 1:
            raise ValueError(f"decode tokens must be (max_seqs, 1), got "
                             f"{tokens.shape}")
        if block_tables is None or lengths is None or block_ids is None \
                or offsets is None:
            raise ValueError("paged decode needs block_tables, lengths, "
                             "append_block_ids and append_offsets")
        lengths = jnp.asarray(lengths, jnp.int32)
        # copy-on-write FIRST: pending shared blocks become private
        # before this step reads or writes them (null pairs no-op, so
        # the program shape never changes — zero-recompile)
        if cow_src is not None:
            cache = cache.cow_copy(jnp.asarray(cow_src, jnp.int32),
                                   jnp.asarray(cow_dst, jnp.int32))
        with jax.named_scope("gpt_embed"):
            h = self._word_rows(params, tokens)
            pos = jnp.take(
                params["embedding"]["position"],
                jnp.clip(lengths, 0, cfg.max_position_embeddings - 1),
                axis=0)[:, None]
            x = (h + pos).astype(cfg.compute_dtype)

        block_ids = jnp.asarray(block_ids, jnp.int32)
        offsets = jnp.asarray(offsets, jnp.int32)

        # the kernel's walk is every layer's: made once, outside the scan
        x, cache = self._scan_paged_layers(
            self._paged_decode_layer, params, x, cache, block_tables,
            lengths, block_ids, offsets, mean_context,
            paged_work_list(lengths, cache.block_size,
                            block_tables.shape[1]))
        x = self._ln(params["final_ln"], x)
        return self.logits(params, x)[:, 0], cache

    def _scan_paged_layers(self, layer_fn, params, x, cache, *args):
        """``layer_fn(lp, x, cache, layer, *args) -> (x, cache)`` over the
        layer stack. The pool is CARRIED, not scanned over: a scan's xs
        are sliced per layer, and a slice of the pool is a copy of it."""
        cfg = self.cfg

        def body(carry, layer_lp):
            layer, lp = layer_lp
            return layer_fn(lp, *carry, layer, *args), None

        carry, _ = scan_stable_vma(
            body, (x, cache),
            (jnp.arange(cfg.num_layers, dtype=jnp.int32), params["layers"]),
            unroll=cfg.layer_scan_unroll)
        return carry

    # -- serving: speculative k-token verify --------------------------------

    def _verify_embed(self, params, tokens, lengths):
        """Embed ``tokens (S, Q)`` at positions ``lengths + [0..Q)`` —
        row i of the verify window sits where sequential decode step i
        would have put it."""
        cfg = self.cfg
        Q = tokens.shape[1]
        with jax.named_scope("gpt_embed"):
            h = self._word_rows(params, tokens)
            positions = lengths[:, None] + jnp.arange(Q)[None, :]
            pos = jnp.take(
                params["embedding"]["position"],
                jnp.clip(positions, 0, cfg.max_position_embeddings - 1),
                axis=0)                                # (S, Q, hidden)
            return (h + pos).astype(cfg.compute_dtype)

    def _verify_qkv(self, lp, h):
        """(S, Q, 3*hidden) -> rank-4 ``q, k_new, v_new`` (S, H, Q, D)
        plus their cache store+load images for the cross-draft merge."""
        cfg = self.cfg
        from apex_tpu.serving.cache import store_roundtrip
        qkv, _ = self.qkv(lp["qkv"], h)
        S, Q = qkv.shape[:2]
        qkv = qkv.reshape(S, Q, cfg.num_attention_heads,
                          3 * cfg.head_dim).transpose(0, 2, 1, 3)
        return jnp.split(qkv, 3, axis=-1), store_roundtrip

    def _paged_verify_layer(self, lp: dict, x: jnp.ndarray, cache, layer,
                            block_tables: jnp.ndarray,
                            lengths: jnp.ndarray, block_ids: jnp.ndarray,
                            offsets: jnp.ndarray,
                            mean_context: Optional[float], work):
        """One layer of the verify step: ``x`` is ``(S, Q, hidden)`` — the
        last accepted token plus the in-flight drafts. The bounded
        block-table fetch of :meth:`_paged_decode_layer` is amortized
        over the Q rows; causality among them is the exact LSE merge
        inside :func:`paged_decode_attention`, fed the cache-dtype
        store+load images so the numerics match Q sequential steps; the
        layer then writes the whole window (rejected rows land above
        the cursor, see ``PagedKVCache.append_k``)."""
        cfg = self.cfg
        h = self._ln(lp["ln1"], x)
        with jax.named_scope("gpt_attention"):
            (q, k_new, v_new), roundtrip = self._verify_qkv(lp, h)
            ctx = paged_decode_attention(
                q, cache.k, cache.v, layer, block_tables, lengths,
                k_new=k_new, v_new=v_new, k_scale=cache.k_scale,
                v_scale=cache.v_scale, mean_context=mean_context,
                use_pallas=cfg.use_flash, work=work,
                k_cast=roundtrip(k_new, cache.k.dtype, cache.quantized),
                v_cast=roundtrip(v_new, cache.k.dtype, cache.quantized))
            cache = cache.append_k(layer, k_new, v_new, block_ids, offsets)
            S, _, Q, _ = ctx.shape
            out, _ = self.proj(lp["proj"],
                               ctx.transpose(0, 2, 1, 3).reshape(S, Q, -1))
        x = x + out
        x = x + self._mlp(lp, self._ln(lp["ln2"], x))
        return x, cache

    def verify_forward(self, params: dict, tokens: jnp.ndarray, kv_cache,
                       block_tables: jnp.ndarray, lengths: jnp.ndarray,
                       append_block_ids: jnp.ndarray,
                       append_offsets: jnp.ndarray,
                       cow_src: Optional[jnp.ndarray] = None,
                       cow_dst: Optional[jnp.ndarray] = None,
                       mean_context: Optional[float] = None):
        """Speculative verify: score ``tokens (max_seqs, Q)`` — each
        slot's last accepted token plus its ``Q - 1`` drafts — in ONE
        pass over the cached prefix. Returns ``(logits (S, Q, vocab),
        cache)``.

        The pool takes the host table/cursor mirrors like the decode
        leg, resolves its COW pairs first, and every layer writes its
        whole window at ``append_block_ids``/``append_offsets`` ``(S,
        Q)`` as it goes (the accepted counts move only the HOST cursor,
        so nothing waits for them)."""
        self._require_cacheable()
        if tokens.ndim != 2:
            raise ValueError(f"verify tokens must be (max_seqs, Q), got "
                             f"{tokens.shape}")
        lengths = jnp.asarray(lengths, jnp.int32)
        block_ids = jnp.asarray(append_block_ids, jnp.int32)
        offsets = jnp.asarray(append_offsets, jnp.int32)
        # copy-on-write FIRST — same sequencing as the decode leg
        if cow_src is not None:
            kv_cache = kv_cache.cow_copy(
                jnp.asarray(cow_src, jnp.int32),
                jnp.asarray(cow_dst, jnp.int32))
        x, kv_cache = self._scan_paged_layers(
            self._paged_verify_layer, params,
            self._verify_embed(params, tokens, lengths), kv_cache,
            block_tables, lengths, block_ids, offsets, mean_context,
            paged_work_list(lengths, kv_cache.block_size,
                            block_tables.shape[1]))
        x = self._ln(params["final_ln"], x)
        return self.logits(params, x), kv_cache     # (S, Q, vocab)

    def sp_grad_sync(self, grads: dict) -> dict:
        """Megatron-LM allreduces the grads of ``sequence_parallel``-marked
        params (the LayerNorms) in a separate pass
        (``allreduce_sequence_parallel_grad``) because torch autograd hands
        back per-rank partials. Here that reduction lives *inside* the
        fused-LN custom_vjp (``reconcile_cotangent`` psums replicated-param
        cotangents over the axes the activations vary on — the same total
        plain-op AD produces), so grads arrive at the optimizer already
        synced and this is an intentional no-op, retained for API parity
        with the Megatron training-loop call sequence."""
        return grads

    # -- pipeline integration ----------------------------------------------

    def stage_fn(self, num_stages: int):
        """Returns ``(stage_fn, split_params)`` for the pipeline schedules:
        the layer stack is split into ``num_stages`` equal chunks; embedding
        and head stay outside (run them in ``loss_fn`` / before feeding
        microbatches), matching build_model's pre/post_process split
        (``schedules/common.py:29-148``)."""
        if self.cfg.num_layers % num_stages:
            raise ValueError(
                f"num_layers ({self.cfg.num_layers}) must be divisible by "
                f"num_stages ({num_stages})")
        if self.cfg.sequence_parallel and num_stages > 1:
            raise NotImplementedError(
                "sequence_parallel does not compose with a real pipeline "
                "split yet: the inter-stage activations would cross the "
                "pipe axis as sequence shards and the shared LN grads "
                "would skip sp_grad_sync. num_stages == 1 (the hybrid "
                "trainer at pp=1) is supported — embed scatters and the "
                "head gathers, mirroring transform()")
        per = self.cfg.num_layers // num_stages

        def stage(stage_params: dict, x: jnp.ndarray, stage_idx) -> jnp.ndarray:
            layer_fn = self.remat_policy.wrap(self._layer)

            def body(x, lp):
                return layer_fn(lp, x), None

            x, _ = scan_stable_vma(body, x, stage_params,
                                   unroll=self.cfg.layer_scan_unroll)
            return x

        def split_params(params: dict):
            """(num_layers, ...) -> (num_stages, per, ...) stage stacking."""
            return jax.tree_util.tree_map(
                lambda p: p.reshape(num_stages, per, *p.shape[1:]),
                params["layers"])

        return stage, split_params

    def pipeline_fns(self, num_stages: int, targets: jnp.ndarray):
        """Full-model pipeline decomposition — embedding INSIDE the
        pipeline: stage 0 embeds tokens (pre_process), the last stage
        applies final LN + tied logits + LM loss (post_process), layer
        chunks in between (``reference:apex/transformer/pipeline_parallel/
        schedules/common.py:29-148``). The embedding + final-LN params are
        pipe-*shared*; the schedules psum their grads over ``pipe``, which
        realizes the tied-embedding allreduce over the embedding group
        (``reference:apex/transformer/parallel_state.py:215-247``,
        ``get_embedding_ranks`` — here the group is carved by grad masking
        rather than a process-group object).

        ``targets``: ``(M, mb, seq)`` int labels for the per-microbatch loss.

        Returns ``(stage_fn, embed_fn, head_loss_fn, split_params,
        shared_of)`` matching the ``shared_params``/``embed_fn`` arguments of
        the pipelined schedules: feed token microbatches ``(M, mb, seq)``
        directly as ``batch``.
        """
        stage, split_params = self.stage_fn(num_stages)

        def shared_of(params: dict) -> dict:
            return {"embedding": params["embedding"],
                    "final_ln": params["final_ln"]}

        def embed_fn(shared: dict, tokens: jnp.ndarray) -> jnp.ndarray:
            return self.embed({"embedding": shared["embedding"]}, tokens)

        def head_loss_fn(shared: dict, y: jnp.ndarray,
                         m: jnp.ndarray) -> jnp.ndarray:
            x = self._ln(shared["final_ln"], y)
            if self.cfg.sequence_parallel:
                # same placement as transform(): LN on the shard, then the
                # invariant gather so the tied head sees the full sequence
                # (and replicated-param grad accounting matches plain TP)
                from apex_tpu.transformer.context_parallel import (
                    gather_from_sequence_parallel_region)
                x = gather_from_sequence_parallel_region(
                    x, TENSOR_AXIS, seq_axis=1, invariant=True)
            logits = self.logits({"embedding": shared["embedding"]}, x)
            tgt = jax.lax.dynamic_index_in_dim(targets, m, 0, keepdims=False)
            if self.cfg.tensor_model_parallel_size > 1:
                per_tok = vocab_parallel_cross_entropy(logits, tgt)
            else:
                per_tok = softmax_cross_entropy_loss(
                    logits.reshape(-1, logits.shape[-1]), tgt.reshape(-1),
                    padding_idx=None, half_to_float=True
                ).reshape(tgt.shape)
            return jnp.mean(per_tok)

        return stage, embed_fn, head_loss_fn, split_params, shared_of

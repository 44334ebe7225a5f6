"""A decoder assembled from a LAYER PATTERN, served through the paged engine.

``GPTModel`` is one block repeated. The architectures deployed today are a
short period of different blocks repeated (``layer_types`` or
``hybrid_override_pattern`` in their ``config.json``). A layer KIND is a
(mixer, feed-forward, cache kind) triple (:class:`LayerKind`,
:data:`LAYER_KINDS`):

- the MIXER is grouped-query attention (with or without rotary positions
  and a window), a Mamba-2 state-space mixer (:mod:`apex_tpu.ops.mamba2`),
  a Mamba-1 selective-scan mixer (:mod:`apex_tpu.ops.mamba1`), latent
  attention (MLA, :mod:`apex_tpu.ops.mla`), or none;
- the FEED-FORWARD is the dropless top-k expert layer
  (:class:`~apex_tpu.transformer.expert_parallel.HeldExpertsMLP`), a dense
  gated MLP (two matrices up, one down: no router, no gather, nothing
  counted), or none;
- the CACHE is a pool of KV blocks a window hands back, a pool that keeps
  them, a pool of latent rows (one a position for all heads), a
  fixed-size per-slot state, or none.

``block`` says how a layer's sub-blocks meet the residual. With
``"parallel"`` (Cohere) a kind's mixer and feed-forward sit side by side
behind one norm, ``x' = x + mixer(n(x)) + ff(n(x))``; with ``"prenorm"``
(Nemotron-H) a layer is ONE sub-block, a mixer or a feed-forward, under a
pre-norm residual; with ``"sequential"`` (Jamba) a layer is TWO, each
behind its own norm: ``x = x + mixer(n1(x)); x' = x + ff(n3(x))``; with
``"sandwich"`` (Ouro) each of the two sub-blocks' outputs passes a norm of
its own too: ``x = x + n2(mixer(n1(x))); x' = x + n4(ff(n3(x)))``.
``norm`` is a bias-free LayerNorm or an RMS norm; the head is the tied
embedding or its own matrix.

``loops`` runs the whole stack that many times over its own output (a
looped language model): pass ``t`` reads the SAME layers' weights and
writes its own K/V, in cache layer ``t * layers + l`` of the kind's pool,
so a block kind's pool stacks ``loops * layers`` layers; the final norm
comes after every pass, the next pass reads the normed state, and the head
reads the last pass's. Positions are the token's own in every pass. With
``loops`` 1 no pass is built.

Five families are written as such patterns:

- Cohere2 sparse: three ``sliding_attention`` layers (rotary, a window)
  then one ``full_attention`` layer (no positional term), each beside a
  gated-SiLU expert layer, parallel blocks, LayerNorm, tied head;
- Nemotron-H: ``mamba`` (``M``), ``moe`` (``E``: squared-ReLU experts in a
  latent, a selection bias, a shared expert of its own width) and
  ``attention`` (``*``: no positional term at all, the Mamba layers carry
  order) layers, pre-norm residual, RMS norm, untied head;
- Jamba: ``mamba_mlp`` (a Mamba-1 mixer with its own RMS norms on ``dt``,
  ``B`` and ``C``) and ``attention_mlp`` (no positional term) layers, each
  followed by a dense gated-SiLU MLP, sequential blocks, RMS norm, tied head;
- Mistral 4 (DeepSeek-V3's layer): ``mla_moe`` layers only, latent
  attention then the expert layer with a selection bias and one shared
  expert, sequential blocks, RMS norm, untied head;
- Ouro (a looped stack): ``rope_attention_mlp`` layers only, attention with
  rotary positions paired ``(j, j + d/2)`` then a dense gated-SiLU MLP,
  sandwich blocks, RMS norm, untied head, the stack run ``loops`` times.

The latent mixer (``mla``): the query through its own latent,
``q = rms(x Wq_a) Wq_b`` split a head into a part with no position and a
part rotated by YaRN's table, pairs interleaved; ONE cached row a
position, ``[c | k_pe] = x Wkv_a`` with ``c`` under its own RMS norm and
``k_pe`` rotated, shared by every head; with ``llama4_scaling_beta`` the
query times ``1 + beta ln(1 + floor(p / rope_original))``. A prefill
expands the row into every head's key ``[c W_uk | k_pe]`` and value ``c
W_uv`` for the flash forward and caches the ROWS; a decode step appends
its row and runs the absorbed kernel over the rows
(:func:`~apex_tpu.ops.mla.mla_decode_attention`), ``W_uk`` folded into
the query and ``W_uv`` applied to the latent context after it.

The layers of one kind are stacked; runs of one kind are scanned, the
period is repeated, and each kind that holds something keeps it in its own
place of the engine's cache (:class:`~apex_tpu.serving.cache.
KindPagedKVCache`): a pool and a block table a block kind, because a window
layer gives back the blocks that fall out of its window and a full layer
never does; one row a slot a state kind (the conv's tail and the SSM
state, in the layout the mixer's kernels read), written by the prefill for its slot and advanced in place by the
decode step.

The model answers the calls ``ServingEngine`` makes of ``GPTModel``
(``cfg``, ``_require_cacheable``, ``forward`` for prefill and decode)
and nothing else: no trainer, no loss, no speculative verify. Weights are
held bfloat16; norm, router, softmax, the state-space decay and state, and
logits are float32.

The chip may hold a SHARE of every layer (``held_experts`` of the
``num_experts`` the router scores, the chip's query heads and their KV
heads, its Mamba heads and groups, its rows of the vocabulary): what the
absent chips would add is left out, the partial ``x'`` goes on to the next
layer. With ``axis_name`` the same code runs inside ``shard_map`` over the
chips that share the layers and the parts are summed there.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.flash_attention import (flash_attention,
                                          paged_decode_attention,
                                          paged_work_list)
from apex_tpu.ops.mamba1 import mamba1_decode_update, mamba1_selective_scan
from apex_tpu.ops.mamba2 import (causal_conv, causal_conv_update,
                                 mamba2_chunk_scan, mamba2_decode_update)
from apex_tpu.ops.mla import mla_decode_attention, yarn_inv_freq, yarn_mscale
from apex_tpu.transformer.expert_parallel import HeldExpertsMLP

__all__ = ["LayerKind", "LAYER_KINDS", "PatternDecoderConfig",
           "PatternDecoder"]


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What a ``layer_types`` entry stands for (module docstring)."""

    mixer: Optional[str]   # "attention" | "mamba2" | "mamba1" | "mla" | None
    feed_forward: Optional[str]   # "experts" | "dense" | None
    cache: Optional[str]   # "blocks" | "window_blocks" | "latent" | "state"
    #                        | None
    rotary: Optional[str] = None  # attention: rotary positions, pairs
    #                               "interleaved" (2j, 2j + 1) | "half"
    #                               (j, j + d/2)

    @property
    def windowed(self) -> bool:
        return self.cache == "window_blocks"


LAYER_KINDS: Dict[str, LayerKind] = {
    "sliding_attention": LayerKind("attention", "experts", "window_blocks",
                                   rotary="interleaved"),
    "full_attention": LayerKind("attention", "experts", "blocks"),
    # the names nemotron_h's hybrid_override_pattern letters map to
    "mamba": LayerKind("mamba2", None, "state"),            # M
    "moe": LayerKind(None, "experts", None),                # E
    "attention": LayerKind("attention", None, "blocks"),    # *
    # jamba's two layers, each a mixer THEN a dense MLP
    "mamba_mlp": LayerKind("mamba1", "dense", "state"),
    "attention_mlp": LayerKind("attention", "dense", "blocks"),
    # mistral 4's one layer: latent attention THEN the expert layer
    "mla_moe": LayerKind("mla", "experts", "latent"),
    # ouro's one layer: rotary attention (rotate-half pairs) THEN a dense MLP
    "rope_attention_mlp": LayerKind("attention", "dense", "blocks",
                                    rotary="half"),
}


@dataclasses.dataclass(frozen=True)
class PatternDecoderConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]          # one entry a layer
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    # the expert layer's sizes (a model with no "experts" kind leaves them)
    expert_size: int = 0                  # every routed (riding shared) expert
    num_experts: int = 0                  # the router's width
    num_experts_per_tok: int = 0
    held_experts: Tuple[int, ...] = ()
    num_shared_experts: int = 0
    sliding_window: int = 0               # positions, the query's own counted
    rope_theta: float = 10000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    max_position_embeddings: int = 8192
    compute_dtype: jnp.dtype = jnp.bfloat16
    params_dtype: jnp.dtype = jnp.bfloat16
    init_std: float = 0.02
    use_flash: Optional[bool] = None      # None: the kernels' own gates
    use_grouped_experts: bool = True      # False: the experts x tokens oracle
    axis_name: Optional[str] = None
    block: str = "parallel"               # | "prenorm": one sub-block a
    #                                       layer | "sequential": two |
    #                                       "sandwich": two, each output
    #                                       normed too
    loops: int = 1                        # passes of the whole stack
    norm: str = "layernorm"               # | "rmsnorm"
    tie_embeddings: bool = True           # False: the head is ``lm_head``
    # the expert layer's form (HeldExpertsMLP)
    expert_activation: str = "swiglu"     # | "relu2"
    router_bias: bool = False
    routed_scaling: float = 1.0
    latent_size: Optional[int] = None
    shared_expert_size: Optional[int] = None
    # the Mamba-2 mixer
    mamba_heads: int = 0
    mamba_head_dim: int = 64
    mamba_groups: int = 1
    mamba_state: int = 128
    mamba_conv: int = 4
    mamba_chunk: int = 128
    # the Mamba-1 mixer (it reads mamba_state, mamba_conv, mamba_chunk too)
    mamba1_inner: int = 0                 # channels: expand * hidden
    mamba1_dt_rank: int = 0
    # the dense gated MLP
    intermediate_size: int = 0
    # the latent-attention mixer (module docstring): head_dim is the
    # query-key head, qk_nope_head_dim + qk_rope_head_dim
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_factor: float = 1.0              # YaRN's stretch (1: none)
    rope_original: int = 0                # the positions it stretches
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 0.0      # > 0: YaRN's factor squared in
    #                                       the softmax scale
    llama4_scaling_beta: float = 0.0      # 0: no query scaling

    def __post_init__(self):
        unknown = set(self.layer_types) - set(LAYER_KINDS)
        if unknown or not self.layer_types:
            raise ValueError(f"layer_types {sorted(unknown)} not among "
                             f"{sorted(LAYER_KINDS)}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads do not group over "
                f"{self.num_key_value_heads} KV heads")
        if self.head_dim % 2:
            raise ValueError("rotary pairs need an even head_dim")
        if self.block not in ("parallel", "prenorm", "sequential",
                              "sandwich") \
                or self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"block {self.block!r} / norm {self.norm!r}")
        if self.loops < 1:
            raise ValueError(f"loops {self.loops} < 1")
        unlooped = {LAYER_KINDS[k].cache for k in self.layer_types} \
            - {None, "blocks", "window_blocks"}
        if self.loops > 1 and unlooped:
            # a pass keeps its own K/V blocks; a per-slot state or a latent
            # pool a pass is not written
            raise ValueError(f"a looped stack keeps KV blocks only, not "
                             f"{sorted(unlooped)}")
        for kind in set(self.layer_types):
            k = LAYER_KINDS[kind]
            if self.block == "prenorm" and (k.mixer is None) \
                    != (k.feed_forward is not None):
                raise ValueError(
                    f"a prenorm layer is one sub-block; {kind!r} is {k}")
            if self.block in ("sequential", "sandwich") and (
                    k.mixer is None or k.feed_forward is None):
                raise ValueError(
                    f"a {self.block} layer is a mixer then a feed-forward; "
                    f"{kind!r} is {k}")
            if k.feed_forward == "experts" and self.num_experts < 1:
                raise ValueError(f"{kind!r} needs the expert layer's sizes")
            if k.feed_forward == "dense" and self.intermediate_size < 1:
                raise ValueError(f"{kind!r} needs intermediate_size")
            if k.mixer == "mamba1" and (self.mamba1_inner < 1
                                        or self.mamba1_dt_rank < 1):
                raise ValueError(
                    f"{kind!r} needs mamba1_inner and mamba1_dt_rank")
            if k.mixer == "mla" and not (
                    self.q_lora_rank > 0 and self.kv_lora_rank > 0
                    and self.qk_nope_head_dim > 0
                    and self.qk_rope_head_dim % 2 == 0 < self.qk_rope_head_dim
                    and self.head_dim == self.qk_nope_head_dim
                    + self.qk_rope_head_dim == self.v_head_dim):
                # the prefill's flash forward takes one head width for the
                # query-key product and the values
                raise ValueError(
                    f"{kind!r} needs the latent ranks and head_dim = "
                    "qk_nope_head_dim + qk_rope_head_dim (even) = "
                    "v_head_dim")
            if k.mixer == "mamba2" and (
                    self.mamba_heads < 1
                    or self.mamba_heads % self.mamba_groups):
                raise ValueError(
                    f"{self.mamba_heads} Mamba heads do not divide over "
                    f"{self.mamba_groups} groups")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def period(self) -> Tuple[str, ...]:
        """The shortest prefix of ``layer_types`` whose repetition is the
        whole list."""
        types = self.layer_types
        for n in range(1, len(types) + 1):
            if len(types) % n == 0 and types[:n] * (len(types) // n) == types:
                return types[:n]
        return types

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """The period as runs of one kind: ``(kind, layers in the run,
        layers of that kind before it in the period)``."""
        out, seen = [], {}
        for kind in self.period:
            if out and out[-1][0] == kind:
                out[-1][1] += 1
            else:
                out.append([kind, 1, seen.get(kind, 0)])
            seen[kind] = seen.get(kind, 0) + 1
        return tuple(tuple(r) for r in out)

    def layers_of(self, kind: str) -> int:
        return self.layer_types.count(kind)

    @property
    def kinds(self) -> Tuple[str, ...]:
        """The layer kinds in the period's order."""
        return tuple(dict.fromkeys(self.period))

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def mamba_conv_channels(self) -> int:
        """x, B and C pass the conv: ``heads * head_dim + 2 groups *
        state``."""
        return self.mamba_inner + 2 * self.mamba_groups * self.mamba_state

    @property
    def cache_kinds(self) -> dict:
        """What the engine builds its cache from, in the period's order and
        for the kinds that hold something only: ``{kind: (cached layers,
        window or None)}`` for a kind that keeps KV blocks (``loops`` times
        its layers: a pass keeps its own K/V), a
        :class:`~apex_tpu.serving.cache.StateSpec` for one that keeps a
        per-slot state, a :class:`~apex_tpu.serving.cache.LatentSpec` for
        one that keeps latent rows."""
        from apex_tpu.serving.cache import LatentSpec, StateSpec
        out = {}
        for kind in self.kinds:
            holds = LAYER_KINDS[kind].cache
            if holds == "latent":
                out[kind] = LatentSpec(self.layers_of(kind),
                                       self.kv_lora_rank,
                                       self.qk_rope_head_dim)
            elif holds == "state" and LAYER_KINDS[kind].mixer == "mamba1":
                out[kind] = StateSpec(
                    self.layers_of(kind), self.mamba1_inner, self.mamba_conv,
                    1, self.mamba1_inner, self.mamba_state,
                    layout="channels_last")
            elif holds == "state":
                out[kind] = StateSpec(
                    self.layers_of(kind), self.mamba_conv_channels,
                    self.mamba_conv, self.mamba_heads, self.mamba_head_dim,
                    self.mamba_state)
            elif holds is not None:
                out[kind] = (self.loops * self.layers_of(kind),
                             self.sliding_window
                             if holds == "window_blocks" else None)
        return out


def rotary_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                       theta: float, inv=None) -> jnp.ndarray:
    """Rotary positions over the whole head, pairs ``(2j, 2j + 1)``
    (GPT-J's layout): ``x`` ``(..., tokens, heads, d)``, ``positions``
    ``(..., tokens)``; ``inv`` the ``d // 2`` frequencies where they are
    not ``theta``'s own (YaRN's table). Float32 inside."""
    d = x.shape[-1]
    if inv is None:
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pair = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pair[..., 0], pair[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def rotary_half(x: jnp.ndarray, positions: jnp.ndarray,
                theta: float) -> jnp.ndarray:
    """Rotary positions over the whole head, pairs ``(j, j + d/2)``
    (``transformers``' ``rotate_half``): ``x cos + rotate_half(x) sin``
    with ``rotate_half([a, b]) = [-b, a]`` over the head's two halves and
    ``inv_j = theta^(-2j/d)``; shapes as :func:`rotary_interleaved`.
    Float32 inside."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _rms(x, gain, eps):
    """``x * rsqrt(mean(x^2) + eps) * gain`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain.astype(jnp.float32)


class PatternDecoder:
    """See the module docstring."""

    def __init__(self, config: PatternDecoderConfig):
        self.cfg = cfg = config
        #: the expert layer, or None for a model whose configuration
        #: describes none (``num_experts`` 0: nothing is built for it, no
        #: router, no stacked weights)
        self.experts = HeldExpertsMLP(
            cfg.hidden_size, cfg.expert_size, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.held_experts,
            cfg.num_shared_experts, axis_name=cfg.axis_name,
            params_dtype=cfg.params_dtype, init_std=cfg.init_std,
            use_pallas=cfg.use_grouped_experts,
            activation=cfg.expert_activation, select_bias=cfg.router_bias,
            scaling=cfg.routed_scaling, latent_size=cfg.latent_size,
            shared_size=cfg.shared_expert_size) if cfg.num_experts else None

    @property
    def step_stats(self) -> bool:
        """Whether the decode and prefill programs' ``stats`` hold anything
        (the engine then packs them behind the sampled tokens): per layer
        that has an EXPERT layer, in the order the layers run, the
        assignments that landed on each held expert and the tokens with no
        held pick — ``(expert layers, len(held_experts) + 1)`` int32. A
        model with none has no rows and its token fetch carries nothing."""
        return self.stats_shape[0] > 0

    @property
    def stats_shape(self) -> Tuple[int, int]:
        cfg = self.cfg
        return (cfg.loops * sum(LAYER_KINDS[k].feed_forward == "experts"
                                for k in cfg.layer_types),
                len(cfg.held_experts) + 1)

    @property
    def stats_names(self) -> Tuple[str, ...]:
        """The counter each column of ``stats`` adds to (the scheduler
        sums the rows and puts ``serve/`` before the name)."""
        return tuple(f"expert_assignments/{e}"
                     for e in range(len(self.cfg.held_experts))) \
            + ("tokens_without_held_pick",)

    # -- parameters ---------------------------------------------------------

    def param_shapes(self) -> dict:
        cfg = self.cfg
        H, d = cfg.hidden_size, cfg.head_dim
        q, kv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
        inner, nh = cfg.mamba_inner, cfg.mamba_heads
        E, R, N = cfg.mamba1_inner, cfg.mamba1_dt_rank, cfg.mamba_state
        F = cfg.intermediate_size
        feed_forwards = {
            "experts": lambda: self.experts.param_shapes(),
            "dense": lambda: {"mlp_gate": (H, F), "mlp_up": (H, F),
                              "mlp_down": (F, H)},
            None: dict}
        mixers = {
            "attention": {"wq": (H, q), "wk": (H, kv), "wv": (H, kv),
                          "wo": (q, H)},
            "mamba2": {"in_proj": (H, inner + cfg.mamba_conv_channels + nh),
                       "conv_w": (cfg.mamba_conv_channels, cfg.mamba_conv),
                       "conv_b": (cfg.mamba_conv_channels,),
                       "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
                       "gate_norm": (inner,), "out_proj": (inner, H)},
            # A_log is LANE-MAJOR, (state, channels), as the state is held
            "mamba1": {"in_proj": (H, 2 * E), "conv_w": (E, cfg.mamba_conv),
                       "conv_b": (E,), "x_proj": (E, R + 2 * N),
                       "dt_norm": (R,), "b_norm": (N,), "c_norm": (N,),
                       "dt_proj": (R, E), "dt_bias": (E,), "A_log": (N, E),
                       "D": (E,), "out_proj": (E, H)},
            "mla": self._mla_shapes(),
            None: {}}
        layers = {}
        for kind in cfg.kinds:
            k = LAYER_KINDS[kind]
            per_layer = dict({"norm": (H,)}, **mixers[k.mixer])
            if cfg.block in ("sequential", "sandwich"):
                per_layer["ff_norm"] = (H,)
            if cfg.block == "sandwich":
                per_layer.update(mixer_out_norm=(H,), ff_out_norm=(H,))
            per_layer.update(feed_forwards[k.feed_forward]())
            layers[kind] = {name: (cfg.layers_of(kind),) + shape
                            for name, shape in per_layer.items()}
        out = {"embedding": (cfg.vocab_size, H), "final_norm": (H,),
               "layers": layers}
        if not cfg.tie_embeddings:
            out["lm_head"] = (H, cfg.vocab_size)
        if cfg.loops > 1:
            # the exit gate of a looped model, held as published and read
            # by nothing: every token runs every pass (no per-token depth)
            out.update(exit_gate=(H, 1), exit_gate_bias=(1,))
        return out

    def init(self, key: jax.Array) -> dict:
        """Seeded random parameters (tests): matrices N(0, init_std), the
        residual projections scaled down by sqrt(2 L), gains 1 + N; the
        state-space scalars where a decay of a few to a few hundred tokens
        puts them (``dt`` about 0.01-0.1 after its softplus, ``A`` in
        -[1, 16])."""
        cfg = self.cfg
        shapes, treedef = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda s: isinstance(s, tuple))
        out = []
        for i, (path, shape) in enumerate(shapes):
            name = path[-1].key
            k = jax.random.fold_in(key, i)
            x = cfg.init_std * jax.random.normal(k, shape, jnp.float32)
            if name in ("wo", "w_down", "out_proj", "shared_down",
                        "w_latent_up", "mlp_down"):
                x = x / (2.0 * cfg.num_layers) ** 0.5
            if name in ("norm", "final_norm", "gate_norm", "D", "ff_norm",
                        "dt_norm", "b_norm", "c_norm", "q_norm", "kv_norm",
                        "mixer_out_norm", "ff_out_norm"):
                x = 1.0 + x
            if name == "conv_w":
                x = 0.5 * jax.random.normal(k, shape, jnp.float32)
            if name == "dt_bias":
                x = jax.random.uniform(k, shape, jnp.float32, -4.5, -2.5)
            if name == "A_log":
                x = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                               1.0, 16.0))
            out.append(x.astype(cfg.params_dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- pieces -------------------------------------------------------------

    def _norm(self, gain, x):
        if self.cfg.norm == "rmsnorm":
            return _rms(x, gain, self.cfg.layer_norm_eps)
        x = x.astype(jnp.float32)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + self.cfg.layer_norm_eps) \
            * gain.astype(jnp.float32)

    def _qkv(self, lp, h, positions, kind):
        """``h`` ``(T, hidden)`` -> q ``(T, heads, d)``, k, v ``(T, kv
        heads, d)``, rotated on a rotary kind."""
        cfg = self.cfg
        T = h.shape[0]
        dot = lambda w: jnp.dot(h, w, preferred_element_type=jnp.float32
                                ).astype(cfg.compute_dtype)
        q = dot(lp["wq"]).reshape(T, cfg.num_attention_heads, cfg.head_dim)
        k = dot(lp["wk"]).reshape(T, cfg.num_key_value_heads, cfg.head_dim)
        v = dot(lp["wv"]).reshape(T, cfg.num_key_value_heads, cfg.head_dim)
        rotary = LAYER_KINDS[kind].rotary
        if rotary:
            rot = rotary_half if rotary == "half" else rotary_interleaved
            q = rot(q, positions, cfg.rope_theta)
            k = rot(k, positions, cfg.rope_theta)
        return q, k, v

    def _sum_parts(self, part):
        if self.cfg.axis_name is not None:
            part = jax.lax.psum(part, self.cfg.axis_name)
        return part

    def _dense_mlp(self, lp, h):
        """``(silu(h Wgate) * (h Wup)) Wdown``: float32 ``(T, hidden)``,
        this chip's part."""
        cfg = self.cfg
        dot = lambda a, w: jnp.dot(a, w, preferred_element_type=jnp.float32)
        mid = jax.nn.silu(dot(h, lp["mlp_gate"])) * dot(h, lp["mlp_up"])
        return dot(mid.astype(cfg.compute_dtype), lp["mlp_down"])

    def _finish(self, kind, lp, big, li, x, h32, mixed, valid):
        """``x + mixer + feed-forward``, whichever of the two the kind has:
        ``mixed`` is the mixer's part before its output projection joined
        the chips' (float32, or None); the experts read layer ``li`` of the
        kind's stacked weights. In a sequential or sandwich block the
        feed-forward reads its own norm of ``x + mixer``, else the layer's
        one norm ``h32``; in a sandwich block each sub-block's output is
        normed before it joins the residual."""
        cfg = self.cfg
        out_norm = (lambda name, y: self._norm(lp[name], y)) \
            if cfg.block == "sandwich" else (lambda name, y: y)
        acc = x.astype(jnp.float32)
        if mixed is not None:
            acc = acc + out_norm("mixer_out_norm", self._sum_parts(mixed))
        feed_forward = LAYER_KINDS[kind].feed_forward
        no_stats = jnp.zeros((0, len(cfg.held_experts) + 1), jnp.int32)
        if feed_forward is None:
            return acc.astype(cfg.compute_dtype), no_stats
        if cfg.block in ("sequential", "sandwich"):
            # the residual is rounded to the compute dtype between the two
            # sub-blocks, as between two layers
            acc = acc.astype(cfg.compute_dtype)
            h32 = self._norm(lp["ff_norm"], acc)
            acc = acc.astype(jnp.float32)
        if feed_forward == "dense":
            out = out_norm("ff_out_norm", self._sum_parts(
                self._dense_mlp(lp, h32.astype(cfg.compute_dtype))))
            return (acc + out).astype(cfg.compute_dtype), no_stats
        small = {n: v for n, v in lp.items()
                 if n in self.experts.param_shapes()}
        moe, stats = self.experts(
            dict(big, **small), h32.astype(cfg.compute_dtype), valid=valid,
            layer=li)
        x = (acc + out_norm("ff_out_norm", moe.astype(jnp.float32))).astype(
            cfg.compute_dtype)
        return x, jnp.concatenate([stats["load"],
                                   stats["no_held_pick"][None]])[None]

    def _window(self, kind):
        return self.cfg.sliding_window if LAYER_KINDS[kind].windowed \
            else None

    # -- the Mamba-2 mixer ----------------------------------------------------

    def _mamba_in(self, lp, h):
        """``[z | xBC | dt] = h Win``: the gate ``z`` and ``dt`` (after
        its bias and softplus) float32, what passes the conv in the compute
        dtype; and ``A = -exp(A_log)``."""
        cfg = self.cfg
        inner, conv = cfg.mamba_inner, cfg.mamba_conv_channels
        proj = jnp.dot(h, lp["in_proj"], preferred_element_type=jnp.float32)
        z, xbc, dt = jnp.split(proj, (inner, inner + conv), axis=-1)
        dt = jax.nn.softplus(dt + lp["dt_bias"].astype(jnp.float32))
        return z, xbc.astype(cfg.compute_dtype), dt, \
            -jnp.exp(lp["A_log"].astype(jnp.float32))

    def _mamba_split(self, xbc):
        """``[x | B | C]`` of the conv's output: ``(T, heads, head_dim)``,
        ``(T, groups, state)`` twice."""
        cfg = self.cfg
        T, gn = xbc.shape[0], cfg.mamba_groups * cfg.mamba_state
        x, B, C = jnp.split(xbc, (cfg.mamba_inner, cfg.mamba_inner + gn),
                            axis=-1)
        return (x.reshape(T, cfg.mamba_heads, cfg.mamba_head_dim),
                B.reshape(T, cfg.mamba_groups, cfg.mamba_state),
                C.reshape(T, cfg.mamba_groups, cfg.mamba_state))

    def _mamba_out(self, lp, y, x, z):
        """The skip ``D x``, the gate THEN the norm (RMS over each group's
        channels apart), the output projection: float32 ``(T, hidden)``,
        this chip's part."""
        cfg = self.cfg
        T = y.shape[0]
        y = y + lp["D"].astype(jnp.float32)[None, :, None] \
            * x.astype(jnp.float32)
        y = y.reshape(T, cfg.mamba_inner) * jax.nn.silu(z)
        g = y.reshape(T, cfg.mamba_groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                              + cfg.layer_norm_eps)
        y = g.reshape(T, cfg.mamba_inner) \
            * lp["gate_norm"].astype(jnp.float32)
        return jnp.dot(y.astype(cfg.compute_dtype), lp["out_proj"],
                       preferred_element_type=jnp.float32)

    def _mamba_prefill(self, kind, lp, li, h, cache, prompt_len, slot):
        """The mixer over one prompt: the chunked scan from a zero state;
        with a cache the conv's tail and the state of the LAST REAL token
        overwrite the slot's rows."""
        cfg = self.cfg
        z, xbc, dt, A = self._mamba_in(lp, h)
        xbc, tail = causal_conv(xbc, lp["conv_w"], lp["conv_b"], prompt_len)
        x, B, C = self._mamba_split(xbc)
        y, state = mamba2_chunk_scan(
            x, dt, A, B, C, chunk=min(cfg.mamba_chunk, h.shape[0]),
            length=prompt_len, use_pallas=cfg.use_flash)
        if cache is not None:
            cache = dict(cache, **{kind: cache[kind].write_slot(
                li, slot, tail, state)})
        return self._mamba_out(lp, y, x, z), cache

    def _mamba_decode(self, kind, lp, li, h, cache, valid):
        """One token a slot: the conv over the slot's tail, the state moved
        on by one token, both written back where they lie; an idle slot's
        rows stay as they were."""
        held = cache[kind]
        z, xbc, dt, A = self._mamba_in(lp, h)
        tail = held.tails(li)
        xbc, moved = causal_conv_update(tail, xbc, lp["conv_w"],
                                        lp["conv_b"])
        x, B, C = self._mamba_split(xbc)
        y, state = mamba2_decode_update(held.ssm[li], x, dt, A, B, C, valid)
        moved = jnp.where(valid[:, None, None], moved, tail)
        cache = dict(cache, **{kind: held.write_layer(li, moved, state)})
        return self._mamba_out(lp, y, x, z), cache

    # -- the Mamba-1 mixer ----------------------------------------------------

    def _mamba1_in(self, lp, h):
        """``[u0 | z] = h Win``: what passes the conv in the compute dtype,
        the gate ``z`` float32."""
        proj = jnp.dot(h, lp["in_proj"], preferred_element_type=jnp.float32)
        u0, z = jnp.split(proj, 2, axis=-1)
        return u0.astype(self.cfg.compute_dtype), z

    def _mamba1_ssm_in(self, lp, u):
        """What the recurrence reads of the conv's output ``u`` ``(T, E)``:
        ``[dt | B | C] = u Wx``, each under Jamba's own RMS norm, ``delta =
        softplus(dt Wdt + b)``, and ``A = -exp(A_log)`` ``(N, E)``. All
        float32."""
        cfg = self.cfg
        R, N = cfg.mamba1_dt_rank, cfg.mamba_state
        proj = jnp.dot(u, lp["x_proj"], preferred_element_type=jnp.float32)
        dt, B, C = jnp.split(proj, (R, R + N), axis=-1)
        rms = lambda x, g: _rms(x, g, cfg.layer_norm_eps)
        dt = rms(dt, lp["dt_norm"]).astype(cfg.compute_dtype)
        delta = jax.nn.softplus(
            jnp.dot(dt, lp["dt_proj"], preferred_element_type=jnp.float32)
            + lp["dt_bias"].astype(jnp.float32))
        return delta, rms(B, lp["b_norm"]), rms(C, lp["c_norm"]), \
            -jnp.exp(lp["A_log"].astype(jnp.float32))

    def _mamba1_out(self, lp, y, u, z):
        """The skip ``D u``, the gate, the output projection: float32 ``(T,
        hidden)``, this chip's part."""
        y = (y + lp["D"].astype(jnp.float32) * u.astype(jnp.float32)) \
            * jax.nn.silu(z)
        return jnp.dot(y.astype(self.cfg.compute_dtype), lp["out_proj"],
                       preferred_element_type=jnp.float32)

    def _mamba1_prefill(self, kind, lp, li, h, cache, prompt_len, slot):
        """The mixer over one prompt: the selective scan from a zero state;
        with a cache the conv's tail and the state of the LAST REAL token
        overwrite the slot's rows."""
        cfg = self.cfg
        u0, z = self._mamba1_in(lp, h)
        u, tail = causal_conv(u0, lp["conv_w"], lp["conv_b"], prompt_len)
        delta, B, C, A = self._mamba1_ssm_in(lp, u)
        y, state = mamba1_selective_scan(
            u, delta, A, B, C, chunk=min(cfg.mamba_chunk, h.shape[0]),
            length=prompt_len, use_pallas=cfg.use_flash)
        if cache is not None:
            cache = dict(cache, **{kind: cache[kind].write_slot(
                li, slot, tail, state)})
        return self._mamba1_out(lp, y, u, z), cache

    def _mamba1_decode(self, kind, lp, li, h, cache, valid):
        """One token a slot, as :meth:`_mamba_decode`: the layer's rows of
        the stacked state ``(L, S, N, E)`` moved on where they lie."""
        held = cache[kind]
        u0, z = self._mamba1_in(lp, h)
        tail = held.tails(li)
        u, moved = causal_conv_update(tail, u0, lp["conv_w"], lp["conv_b"])
        delta, B, C, A = self._mamba1_ssm_in(lp, u)
        y, ssm = mamba1_decode_update(held.ssm, li, u, delta, A, B, C, valid,
                                      use_pallas=self.cfg.use_flash)
        moved = jnp.where(valid[:, None, None], moved, tail)
        cache = dict(cache, **{kind: dataclasses.replace(
            held.write_tails(li, moved), ssm=ssm)})
        return self._mamba1_out(lp, y, u, z), cache

    # -- the latent-attention (MLA) mixer -------------------------------------

    def _mla_shapes(self) -> dict:
        cfg = self.cfg
        H, nh = cfg.hidden_size, cfg.num_attention_heads
        dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
        return {"wq_a": (H, qr), "q_norm": (qr,), "wq_b": (qr, nh * (dn + dr)),
                "wkv_a": (H, kr + dr), "kv_norm": (kr,),
                "w_uk": (kr, nh * dn), "w_uv": (kr, nh * cfg.v_head_dim),
                "wo": (nh * cfg.v_head_dim, H)}

    @property
    def mla_scale(self) -> float:
        """The softmax scale: ``1 / sqrt(head_dim)``, times YaRN's attention
        factor squared where ``rope_mscale_all_dim`` is set (DeepSeek-V3's
        attention, whose rotation is then scaled by ``mscale /
        mscale_all_dim``: 1 in every configuration served, so not at all)."""
        cfg = self.cfg
        m = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim) \
            if cfg.rope_mscale_all_dim else 1.0
        return m * m / cfg.head_dim ** 0.5

    def _mla_in(self, lp, h, positions):
        """``h`` ``(T, hidden)`` at ``positions`` ``(T,)`` -> the query
        ``(T, heads, nope + rope)`` float32, its positional part rotated and
        the whole of it scaled by llama 4's rule, and the row the layer
        caches, ``[rms(c) | rotated k_pe]`` ``(T, latent + rope)`` in the
        compute dtype."""
        cfg = self.cfg
        T, nh = h.shape[0], cfg.num_attention_heads
        dn, kr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        dot = lambda a, w: jnp.dot(a, w, preferred_element_type=jnp.float32)
        cq = _rms(dot(h, lp["wq_a"]), lp["q_norm"], cfg.layer_norm_eps)
        q = dot(cq.astype(cfg.compute_dtype), lp["wq_b"]).reshape(T, nh, -1)
        ckv = dot(h, lp["wkv_a"])
        c = _rms(ckv[:, :kr], lp["kv_norm"], cfg.layer_norm_eps)
        inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                            cfg.rope_factor, cfg.rope_original,
                            cfg.rope_beta_fast, cfg.rope_beta_slow)
        rot = lambda x: rotary_interleaved(x, positions, cfg.rope_theta, inv)
        q = jnp.concatenate([q[..., :dn], rot(q[..., dn:])], axis=-1)
        if cfg.llama4_scaling_beta:
            steps = jnp.floor(positions.astype(jnp.float32)
                              / cfg.rope_original)
            q = q * (1.0 + cfg.llama4_scaling_beta
                     * jnp.log1p(steps))[:, None, None]
        return q, jnp.concatenate([c, rot(ckv[:, None, kr:])[:, 0]],
                                  axis=-1).astype(cfg.compute_dtype)

    def _mla_prefill(self, kind, lp, li, h, cache, block_row):
        """The mixer over one prompt: every head's key and value expanded
        from the rows for the flash forward; with a cache the ROWS go into
        the kind's blocks ``block_row[kind]``. Float32 ``(P, hidden)``,
        this chip's part."""
        cfg = self.cfg
        P, nh = h.shape[0], cfg.num_attention_heads
        kr = cfg.kv_lora_rank
        q, row = self._mla_in(lp, h, jnp.arange(P))
        up = lambda w: jnp.dot(row[:, :kr], w,
                               preferred_element_type=jnp.float32).astype(
                                   cfg.compute_dtype).reshape(P, nh, -1)
        k = jnp.concatenate([up(lp["w_uk"]), jnp.broadcast_to(
            row[:, None, kr:], (P, nh, cfg.qk_rope_head_dim))], axis=-1)
        heads_first = lambda x: x.transpose(1, 0, 2)[None]
        with jax.named_scope("pattern_attention"):
            ctx = flash_attention(
                heads_first(q.astype(cfg.compute_dtype)), heads_first(k),
                heads_first(up(lp["w_uv"])), causal=True,
                softmax_scale=self.mla_scale, use_pallas=cfg.use_flash)
        if cache is not None:
            cache = dict(cache, **{kind: cache[kind].write_layer_blocks(
                li, row, block_row[kind])})
        ctx = ctx[0].transpose(1, 0, 2).reshape(P, -1)
        return jnp.dot(ctx.astype(cfg.compute_dtype), lp["wo"],
                       preferred_element_type=jnp.float32), cache

    def _mla_decode(self, kind, lp, li, h, cache, tables, lengths, block_ids,
                    offsets, reads, work, mean_context):
        """One token a slot: its row appended first, then the absorbed
        kernel over the slot's ``reads`` rows (its own included), ``W_uk``
        folded into the query and ``W_uv`` applied to the latent
        context."""
        cfg = self.cfg
        S, nh = h.shape[0], cfg.num_attention_heads
        dn, kr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        q, row = self._mla_in(lp, h, lengths)
        pool = cache[kind].append(li, row, block_ids[kind], offsets)
        q_lat = jnp.einsum("shd,chd->shc",
                           q[..., :dn].astype(cfg.compute_dtype),
                           lp["w_uk"].reshape(kr, nh, dn),
                           preferred_element_type=jnp.float32)
        with jax.named_scope("pattern_attention"):
            ctx = mla_decode_attention(
                q_lat.astype(cfg.compute_dtype),
                q[..., dn:].astype(cfg.compute_dtype), pool.latent, pool.rope,
                li, tables[kind], reads, softmax_scale=self.mla_scale,
                work=work[kind], use_pallas=cfg.use_flash,
                mean_context=mean_context)
        out = jnp.einsum("shc,chd->shd", ctx,
                         lp["w_uv"].reshape(kr, nh, cfg.v_head_dim),
                         preferred_element_type=jnp.float32)
        return jnp.dot(out.reshape(S, -1).astype(cfg.compute_dtype),
                       lp["wo"], preferred_element_type=jnp.float32), \
            dict(cache, **{kind: pool})

    # -- a layer --------------------------------------------------------------

    def _prefill_layer(self, kind, lp, big, li, x, cache, block_row,
                       valid, prompt_len=None, slot=None, ci=None):
        """One layer over one prompt: ``x`` ``(P, hidden)``; with a cache
        an attention layer's K/V go into the pool blocks
        ``block_row[kind]``, a Mamba layer's state into row ``slot``, both
        of cache layer ``ci`` (weights layer ``li``'s in this pass; ``li``
        itself where None)."""
        cfg = self.cfg
        ci = li if ci is None else ci
        P = x.shape[0]
        h32 = self._norm(lp["norm"], x)
        h = h32.astype(cfg.compute_dtype)
        mixer, mixed = LAYER_KINDS[kind].mixer, None
        if mixer == "attention":
            q, k, v = self._qkv(lp, h, jnp.arange(P), kind)
            with jax.named_scope("pattern_attention"):
                ctx = flash_attention(
                    q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
                    v.transpose(1, 0, 2)[None], causal=True,
                    window=self._window(kind), use_pallas=cfg.use_flash)
            ctx = ctx[0].transpose(1, 0, 2).reshape(P, -1)
            if cache is not None:
                cache = dict(cache, **{kind: cache[kind].write_layer_blocks(
                    ci, k.reshape(P, -1), v.reshape(P, -1),
                    block_row[kind])})
            mixed = jnp.dot(ctx.astype(cfg.compute_dtype), lp["wo"],
                            preferred_element_type=jnp.float32)
        elif mixer == "mamba2":
            mixed, cache = self._mamba_prefill(kind, lp, ci, h, cache,
                                               prompt_len, slot)
        elif mixer == "mamba1":
            mixed, cache = self._mamba1_prefill(kind, lp, ci, h, cache,
                                                prompt_len, slot)
        elif mixer == "mla":
            mixed, cache = self._mla_prefill(kind, lp, ci, h, cache,
                                             block_row)
        x, stats = self._finish(kind, lp, big, li, x, h32, mixed, valid)
        return x, cache, stats

    def _paged_decode_layer(self, kind, lp, big, li, x, cache, tables,
                            lengths, block_ids, offsets, valid,
                            mean_context, work, reads=None, ci=None):
        """One layer of the decode step: ``x`` ``(S, hidden)``, one token
        a slot at position ``lengths``, read and written at cache layer
        ``ci`` (weights layer ``li``'s in this pass; ``li`` itself where
        None); ``work`` the paged kernel's walk of each attention kind's
        blocks; ``reads`` the positions a latent kind's kernel reads a slot
        (its own row included, 0 where the slot is idle)."""
        cfg = self.cfg
        ci = li if ci is None else ci
        S = x.shape[0]
        h32 = self._norm(lp["norm"], x)
        h = h32.astype(cfg.compute_dtype)
        mixer, mixed = LAYER_KINDS[kind].mixer, None
        if mixer == "attention":
            q, k_new, v_new = self._qkv(lp, h, lengths, kind)
            pool = cache[kind]
            with jax.named_scope("pattern_attention"):
                ctx = paged_decode_attention(
                    q, pool.k, pool.v, ci, tables[kind], lengths,
                    k_new=k_new, v_new=v_new, mean_context=mean_context,
                    use_pallas=cfg.use_flash, window=self._window(kind),
                    work=work[kind])
            cache = dict(cache, **{kind: pool.append(
                ci, k_new, v_new, block_ids[kind], offsets)})
            mixed = jnp.dot(ctx.reshape(S, -1).astype(cfg.compute_dtype),
                            lp["wo"], preferred_element_type=jnp.float32)
        elif mixer == "mamba2":
            mixed, cache = self._mamba_decode(kind, lp, ci, h, cache, valid)
        elif mixer == "mamba1":
            mixed, cache = self._mamba1_decode(kind, lp, ci, h, cache, valid)
        elif mixer == "mla":
            mixed, cache = self._mla_decode(
                kind, lp, ci, h, cache, tables, lengths, block_ids, offsets,
                reads, work, mean_context)
        x, stats = self._finish(kind, lp, big, li, x, h32, mixed, valid)
        return x, cache, stats

    def _run_layers(self, layer_fn, params, x, cache):
        """The period's runs, the period repeated, the whole ``loops``
        times: ``layer_fn(kind, lp, big, li, ci, x, cache) -> (x, cache,
        stats)``, ``li`` the weights' layer and ``ci`` the cache's (the
        same tracer with ``loops`` 1; ``t * layers + li`` in pass ``t``).
        The experts' stacked weights are closed over and read by layer
        index where they lie; the small per-layer tensors are the scans'
        xs. ``stats``: a row a layer that has an expert layer, a pass. A
        looped stack ends every pass in the final norm (the module
        docstring)."""
        cfg = self.cfg
        n_periods = cfg.num_layers // len(cfg.period)
        in_period = {k: cfg.period.count(k) for k in cfg.kinds}
        stacked = self.experts.stacked if self.experts is not None else ()
        small = {kind: {n: v for n, v in lp.items() if n not in stacked}
                 for kind, lp in params["layers"].items()}
        big = {kind: {n: lp[n] for n in stacked if n in lp}
               for kind, lp in params["layers"].items()}

        def one_period(carry, p, t):
            rows = []
            for kind, count, before in cfg.runs:
                first = p * in_period[kind] + before

                def body(carry, i, kind=kind, first=first):
                    li = first + i
                    ci = li if t is None else t * cfg.layers_of(kind) + li
                    lp = jax.tree_util.tree_map(lambda v: v[li],
                                                small[kind])
                    x, cache, stats = layer_fn(kind, lp, big[kind], li, ci,
                                               *carry)
                    return (x, cache), stats

                carry, stats = jax.lax.scan(
                    body, carry, jnp.arange(count, dtype=jnp.int32))
                rows.append(stats.reshape(-1, stats.shape[-1]))
            return carry, jnp.concatenate(rows, axis=0)

        def one_pass(carry, t):
            if n_periods == 1:
                return one_period(carry, jnp.int32(0), t)
            carry, stats = jax.lax.scan(
                lambda carry, p: one_period(carry, p, t), carry,
                jnp.arange(n_periods, dtype=jnp.int32))
            return carry, stats.reshape(-1, stats.shape[-1])

        if cfg.loops == 1:
            (x, cache), stats = one_pass((x, cache), None)
            return x, cache, stats

        def looped(carry, t):
            (x, cache), stats = one_pass(carry, t)
            x = self._norm(params["final_norm"], x).astype(cfg.compute_dtype)
            return (x, cache), stats

        (x, cache), stats = jax.lax.scan(
            looped, (x, cache), jnp.arange(cfg.loops, dtype=jnp.int32))
        return x, cache, stats.reshape(-1, stats.shape[-1])

    def _logits(self, params, x):
        """``logit_scale * n(x) Whead``, float32; the head is the tied
        embedding or ``lm_head``. A looped stack's ``x`` is normed
        already: its last pass ended in the final norm."""
        cfg = self.cfg
        h = x if cfg.loops > 1 else self._norm(
            params["final_norm"], x).astype(cfg.compute_dtype)
        head = params["embedding"].T if cfg.tie_embeddings \
            else params["lm_head"]
        return cfg.logit_scale * jnp.dot(
            h, head, preferred_element_type=jnp.float32)

    # -- entry points -------------------------------------------------------

    def _require_cacheable(self):
        """The paged engine is the one way this model is served."""

    def __call__(self, params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
        """Logits ``(P, vocab)`` of one sequence ``tokens`` ``(P,)``, no
        cache: the prefill's arithmetic at every position."""
        x = jnp.take(params["embedding"], tokens, axis=0).astype(
            self.cfg.compute_dtype)
        fn = lambda kind, lp, big, li, ci, x, cache: self._prefill_layer(
            kind, lp, big, li, x, cache, None, None, ci=ci)
        x, _, _ = self._run_layers(fn, params, x, None)
        return self._logits(params, x)

    def forward(self, params: dict, tokens: jnp.ndarray, kv_cache=None,
                prompt_len=None, last_logit_only: bool = False,
                block_row=None, block_tables=None, lengths=None,
                append_block_ids=None, append_offsets=None, cow_src=None,
                cow_dst=None, mean_context: Optional[float] = None,
                slot=None):
        """The engine's two legs over a :class:`~apex_tpu.serving.cache.
        KindPagedKVCache` (``block_row``, ``block_tables`` and
        ``append_block_ids`` are dicts by BLOCK kind, as the by-kind
        allocator hands them out):

        - **paged prefill** (``block_row`` given): ``tokens`` ``(1, P)``,
          one prompt right-padded to a bucket; the padding is masked out
          of the routing (it costs no expert row and counts in no
          counter), its K/V land in null blocks and it advances no state:
          row ``slot`` (int32 scalar; a model with a state kind) gets the
          conv tail and the state of the prompt's last real token.
        - **paged decode**: ``tokens`` ``(S, 1)``; a slot whose append
          aims at the null block is idle: routed nowhere, its state rows
          left as they were. The copy-on-write pairs are ignored: this
          model shares no prefix.

        Returns ``(logits, cache, stats)``; ``stats`` as
        :attr:`step_stats` says."""
        from apex_tpu.serving.cache import KindPagedKVCache, NULL_BLOCK
        cfg = self.cfg
        if not isinstance(kv_cache, KindPagedKVCache):
            raise ValueError(
                "PatternDecoder is served from a KindPagedKVCache (one "
                "pool a layer kind) by ServingEngine; got "
                f"{type(kv_cache).__name__}")
        pools = dict(kv_cache.pools)
        if block_row is not None:
            if tokens.ndim != 2 or tokens.shape[0] != 1:
                raise ValueError("prefill is per-request: tokens must be "
                                 f"(1, P), got {tokens.shape}")
            P = tokens.shape[1]
            prompt_len = jnp.clip(jnp.asarray(
                P if prompt_len is None else prompt_len, jnp.int32), 1, P)
            valid = jnp.arange(P) < prompt_len
            x = jnp.take(params["embedding"], tokens[0], axis=0).astype(
                cfg.compute_dtype)
            fn = lambda kind, lp, big, li, ci, x, cache: self._prefill_layer(
                kind, lp, big, li, x, cache, block_row, valid, prompt_len,
                slot, ci=ci)
            x, pools, stats = self._run_layers(fn, params, x, pools)
            if last_logit_only:
                x = jax.lax.dynamic_slice_in_dim(x, prompt_len - 1, 1, 0)
            return (self._logits(params, x)[None],
                    KindPagedKVCache(pools), stats)
        if tokens.ndim != 2 or tokens.shape[1] != 1:
            raise ValueError("decode tokens must be (max_seqs, 1), got "
                             f"{tokens.shape}")
        lengths = jnp.asarray(lengths, jnp.int32)
        first = next(iter(append_block_ids.values()))
        valid = jnp.asarray(first) != NULL_BLOCK
        x = jnp.take(params["embedding"], tokens[:, 0], axis=0).astype(
            cfg.compute_dtype)
        # a latent kind appends its row before its kernel reads: a served
        # slot reads its own row too, an idle one nothing
        latent = [k for k in block_tables if LAYER_KINDS[k].cache == "latent"]
        reads = jnp.where(valid, lengths + 1, 0) if latent else None
        # a kind's layers share one walk: made here, outside their scans
        work = {kind: paged_work_list(
            reads if kind in latent else lengths, pools[kind].block_size,
            table.shape[1], self._window(kind))
                for kind, table in block_tables.items()}
        fn = lambda kind, lp, big, li, ci, x, cache: self._paged_decode_layer(
            kind, lp, big, li, x, cache, block_tables, lengths,
            append_block_ids, append_offsets, valid, mean_context, work,
            reads, ci=ci)
        x, pools, stats = self._run_layers(fn, params, x, pools)
        return self._logits(params, x), KindPagedKVCache(pools), stats

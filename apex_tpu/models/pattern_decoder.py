"""A decoder assembled from a LAYER PATTERN, served through the paged engine.

``GPTModel`` is one block repeated. The architectures deployed today are a
short period of different blocks repeated (``layer_types`` in their
``config.json``): here three ``sliding_attention`` layers (rotary
positions, a window) then one ``full_attention`` layer (no positional term
at all, causal), every layer Cohere's parallel block

    h = n(x);  x' = x + attn(h) + moe(h)

with one bias-free LayerNorm feeding grouped-query attention and a dropless
top-k expert layer (:class:`~apex_tpu.transformer.expert_parallel.
HeldExpertsMLP`) side by side. A layer KIND is a (mixer, feed-forward,
cache kind) triple (:data:`LAYER_KINDS`); the layers of one kind are
stacked and scanned, the period is repeated, and each kind keeps its own
KV pool and block table (:class:`~apex_tpu.serving.cache.KindPagedKVCache`)
because a window layer gives back the blocks that fall out of its window
and a full layer never does.

The model answers the calls ``ServingEngine`` makes of ``GPTModel``
(``cfg``, ``_require_cacheable``, ``forward`` for prefill and decode)
and nothing else: no trainer, no loss, no speculative verify. Weights are held bfloat16; norm, router, softmax and
logits are float32.

The chip may hold a SHARE of every layer (``held_experts`` of the
``num_experts`` the router scores, the chip's query heads and their KV
heads, its rows of the tied embedding): what the absent chips would add is
left out, the partial ``x'`` goes on to the next layer. With
``axis_name`` the same code runs inside ``shard_map`` over the chips that
share the layers and the parts are summed there.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.ops.flash_attention import (flash_attention,
                                          paged_decode_attention)
from apex_tpu.transformer.expert_parallel import HeldExpertsMLP

__all__ = ["LayerKind", "LAYER_KINDS", "PatternDecoderConfig",
           "PatternDecoder"]


@dataclasses.dataclass(frozen=True)
class LayerKind:
    """What a ``layer_types`` entry stands for: the mixer (attention with
    or without rotary positions and a window) and with it the cache kind
    (a pool whose blocks a window returns, or one that keeps them). The
    feed-forward is the expert layer in every kind."""

    rotary: bool
    windowed: bool


# the stacked expert matrices: read by layer index where they lie, never
# sliced a layer (a slice of one kind's gate matrices is a 2 GB copy)
EXPERTS = ("w_gate", "w_up", "w_down")

LAYER_KINDS: Dict[str, LayerKind] = {
    "sliding_attention": LayerKind(rotary=True, windowed=True),
    "full_attention": LayerKind(rotary=False, windowed=False),
}


@dataclasses.dataclass(frozen=True)
class PatternDecoderConfig:
    vocab_size: int
    hidden_size: int
    layer_types: Tuple[str, ...]          # one entry a layer
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    expert_size: int                      # every routed and shared expert
    num_experts: int                      # the router's width
    num_experts_per_tok: int
    held_experts: Tuple[int, ...]
    num_shared_experts: int
    sliding_window: int                   # positions, the query's own counted
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
    def cache_kinds(self) -> Dict[str, Tuple[int, Optional[int]]]:
        """``{kind: (layers, window or None)}`` in the period's order: what
        the engine builds the pools and block tables from."""
        return {kind: (self.layers_of(kind),
                       self.sliding_window if LAYER_KINDS[kind].windowed
                       else None)
                for kind in dict.fromkeys(self.period)}


def rotary_interleaved(x: jnp.ndarray, positions: jnp.ndarray,
                       theta: float) -> jnp.ndarray:
    """Rotary positions over the whole head, pairs ``(2j, 2j + 1)``
    (GPT-J's layout): ``x`` ``(..., tokens, heads, d)``, ``positions``
    ``(..., tokens)``. Float32 inside."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pair = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pair[..., 0], pair[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


class PatternDecoder:
    """See the module docstring."""

    #: the decode and prefill programs return ``stats`` beside the logits
    #: and the cache: per layer, in the order the layers run, the
    #: assignments that landed on each held expert and the tokens with no
    #: held pick — ``(num_layers, len(held_experts) + 1)`` int32
    step_stats = True

    def __init__(self, config: PatternDecoderConfig):
        self.cfg = cfg = config
        self.experts = HeldExpertsMLP(
            cfg.hidden_size, cfg.expert_size, cfg.num_experts,
            cfg.num_experts_per_tok, cfg.held_experts,
            cfg.num_shared_experts, axis_name=cfg.axis_name,
            params_dtype=cfg.params_dtype, init_std=cfg.init_std,
            use_pallas=cfg.use_grouped_experts)

    @property
    def stats_shape(self) -> Tuple[int, int]:
        return (self.cfg.num_layers, len(self.cfg.held_experts) + 1)

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
        n, F = self.experts.num_local, cfg.expert_size
        per_layer = {"norm": (H,), "wq": (H, q), "wk": (H, kv),
                     "wv": (H, kv), "wo": (q, H),
                     "router": (H, cfg.num_experts), "w_gate": (n, H, F),
                     "w_up": (n, H, F), "w_down": (n, F, H)}
        return {"embedding": (cfg.vocab_size, H), "final_norm": (H,),
                "layers": {kind: {name: (cfg.layers_of(kind),) + shape
                                  for name, shape in per_layer.items()}
                           for kind in cfg.cache_kinds}}

    def init(self, key: jax.Array) -> dict:
        """Seeded random parameters (tests): matrices N(0, init_std), the
        residual projections scaled down by sqrt(2 L), gains 1 + N."""
        cfg = self.cfg
        shapes, treedef = jax.tree_util.tree_flatten_with_path(
            self.param_shapes(), is_leaf=lambda s: isinstance(s, tuple))
        out = []
        for i, (path, shape) in enumerate(shapes):
            name = path[-1].key
            x = cfg.init_std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)
            if name in ("wo", "w_down"):
                x = x / (2.0 * cfg.num_layers) ** 0.5
            if name in ("norm", "final_norm"):
                x = 1.0 + x
            out.append(x.astype(cfg.params_dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    # -- pieces -------------------------------------------------------------

    def _norm(self, gain, x):
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
        if LAYER_KINDS[kind].rotary:
            q = rotary_interleaved(q, positions, cfg.rope_theta)
            k = rotary_interleaved(k, positions, cfg.rope_theta)
        return q, k, v

    def _finish(self, lp, big, li, x, h32, ctx, valid):
        """``x + attn + moe`` from the attention context ``(T, heads *
        d)``; the experts read layer ``li`` of the kind's stacked
        weights."""
        cfg = self.cfg
        attn = jnp.dot(ctx.astype(cfg.compute_dtype), lp["wo"],
                       preferred_element_type=jnp.float32)
        if cfg.axis_name is not None:
            attn = jax.lax.psum(attn, cfg.axis_name)
        moe, stats = self.experts(
            dict(big, router=lp["router"]),
            h32.astype(cfg.compute_dtype), valid=valid, layer=li)
        x = (x.astype(jnp.float32) + attn + moe.astype(jnp.float32)
             ).astype(cfg.compute_dtype)
        return x, jnp.concatenate([stats["load"],
                                   stats["no_held_pick"][None]])

    def _window(self, kind):
        return self.cfg.sliding_window if LAYER_KINDS[kind].windowed \
            else None

    def _prefill_layer(self, kind, lp, big, li, x, cache, block_row,
                       valid):
        """One layer over one prompt: ``x`` ``(P, hidden)``; with a cache
        the layer's K/V go into the pool blocks ``block_row[kind]``."""
        cfg = self.cfg
        P = x.shape[0]
        h32 = self._norm(lp["norm"], x)
        q, k, v = self._qkv(lp, h32.astype(cfg.compute_dtype),
                            jnp.arange(P), kind)
        with jax.named_scope("pattern_attention"):
            ctx = flash_attention(
                q.transpose(1, 0, 2)[None], k.transpose(1, 0, 2)[None],
                v.transpose(1, 0, 2)[None], causal=True,
                window=self._window(kind), use_pallas=cfg.use_flash)
        ctx = ctx[0].transpose(1, 0, 2).reshape(P, -1)
        if cache is not None:
            cache = dict(cache, **{kind: cache[kind].write_layer_blocks(
                li, k.reshape(P, -1), v.reshape(P, -1), block_row[kind])})
        x, stats = self._finish(lp, big, li, x, h32, ctx, valid)
        return x, cache, stats

    def _paged_decode_layer(self, kind, lp, big, li, x, cache, tables,
                            lengths, block_ids, offsets, valid,
                            mean_context):
        """One layer of the decode step: ``x`` ``(S, hidden)``, one token
        a slot at position ``lengths``."""
        cfg = self.cfg
        S = x.shape[0]
        h32 = self._norm(lp["norm"], x)
        q, k_new, v_new = self._qkv(lp, h32.astype(cfg.compute_dtype),
                                    lengths, kind)
        pool = cache[kind]
        with jax.named_scope("pattern_attention"):
            ctx = paged_decode_attention(
                q, pool.k, pool.v, li, tables[kind], lengths, k_new=k_new,
                v_new=v_new, mean_context=mean_context,
                use_pallas=cfg.use_flash, window=self._window(kind))
        cache = dict(cache, **{kind: pool.append(
            li, k_new, v_new, block_ids[kind], offsets)})
        x, stats = self._finish(lp, big, li, x, h32, ctx.reshape(S, -1),
                                valid)
        return x, cache, stats

    def _run_layers(self, layer_fn, params, x, cache):
        """The period's runs, the period repeated: ``layer_fn(kind, lp,
        big, li, x, cache) -> (x, cache, stats)``. The experts' stacked
        weights are closed over and read by layer index where they lie;
        the small per-layer tensors are the scans' xs."""
        cfg = self.cfg
        n_periods = cfg.num_layers // len(cfg.period)
        in_period = {k: cfg.period.count(k) for k in cfg.cache_kinds}
        small = {kind: {n: v for n, v in lp.items() if n not in EXPERTS}
                 for kind, lp in params["layers"].items()}
        big = {kind: {n: lp[n] for n in EXPERTS}
               for kind, lp in params["layers"].items()}

        def one_period(carry, p):
            rows = []
            for kind, count, before in cfg.runs:
                first = p * in_period[kind] + before

                def body(carry, i, kind=kind, first=first):
                    li = first + i
                    lp = jax.tree_util.tree_map(lambda v: v[li],
                                                small[kind])
                    x, cache, stats = layer_fn(kind, lp, big[kind], li,
                                               *carry)
                    return (x, cache), stats

                carry, stats = jax.lax.scan(
                    body, carry, jnp.arange(count, dtype=jnp.int32))
                rows.append(stats)
            return carry, jnp.concatenate(rows, axis=0)

        if n_periods == 1:
            (x, cache), stats = one_period((x, cache), jnp.int32(0))
        else:
            (x, cache), stats = jax.lax.scan(
                one_period, (x, cache),
                jnp.arange(n_periods, dtype=jnp.int32))
            stats = stats.reshape(-1, stats.shape[-1])
        return x, cache, stats

    def _logits(self, params, x):
        """``logit_scale * n(x) Wemb^T``, float32."""
        cfg = self.cfg
        h = self._norm(params["final_norm"], x).astype(cfg.compute_dtype)
        return cfg.logit_scale * jnp.dot(
            h, params["embedding"].T, preferred_element_type=jnp.float32)

    # -- entry points -------------------------------------------------------

    def _require_cacheable(self):
        """The paged engine is the one way this model is served."""

    def __call__(self, params: dict, tokens: jnp.ndarray) -> jnp.ndarray:
        """Logits ``(P, vocab)`` of one sequence ``tokens`` ``(P,)``, no
        cache: the prefill's arithmetic at every position."""
        x = jnp.take(params["embedding"], tokens, axis=0).astype(
            self.cfg.compute_dtype)
        fn = lambda kind, lp, big, li, x, cache: self._prefill_layer(
            kind, lp, big, li, x, cache, None, None)
        x, _, _ = self._run_layers(fn, params, x, None)
        return self._logits(params, x)

    def forward(self, params: dict, tokens: jnp.ndarray, kv_cache=None,
                prompt_len=None, last_logit_only: bool = False,
                block_row=None, block_tables=None, lengths=None,
                append_block_ids=None, append_offsets=None, cow_src=None,
                cow_dst=None, mean_context: Optional[float] = None):
        """The engine's two legs over a :class:`~apex_tpu.serving.cache.
        KindPagedKVCache` (``block_row``, ``block_tables`` and
        ``append_block_ids`` are dicts by layer kind, as the by-kind
        allocator hands them out):

        - **paged prefill** (``block_row`` given): ``tokens`` ``(1, P)``,
          one prompt right-padded to a bucket; the padding is masked out
          of the routing (it costs no expert row and counts in no
          counter) and its K/V land in null blocks.
        - **paged decode**: ``tokens`` ``(S, 1)``; a slot whose append
          aims at the null block is idle and routed nowhere. The
          copy-on-write pairs are ignored: this model shares no prefix.

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
            fn = lambda kind, lp, big, li, x, cache: self._prefill_layer(
                kind, lp, big, li, x, cache, block_row, valid)
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
        fn = lambda kind, lp, big, li, x, cache: self._paged_decode_layer(
            kind, lp, big, li, x, cache, block_tables, lengths,
            append_block_ids, append_offsets, valid, mean_context)
        x, pools, stats = self._run_layers(fn, params, x, pools)
        return self._logits(params, x), KindPagedKVCache(pools), stats

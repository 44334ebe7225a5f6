"""The serving engine: AOT-compiled prefill, decode and verify programs
over a donated block pool.

The engine owns the pool (:class:`~apex_tpu.serving.cache.PagedKVCache`,
or one pool a layer kind), its host-side allocator and the programs a
serving process runs forever:

- **prefill**: one request's padded prompt ``(1, bucket)`` through the
  ordinary causal forward (the training flash path), K/V written into
  the pool blocks the allocator mapped for the slot, the first output
  token sampled from the logits at the prompt's true last position. One
  program a prompt-length bucket;
- **decode**: ONE token for EVERY slot ``(max_seqs, 1)`` through the
  paged decode kernel, each slot's context read through its block
  table, K/V appended at each slot's cursor, next tokens sampled;
- **verify** (``speculate_k > 0``): each slot's last token plus ``k``
  drafts scored in one pass;
- **release**: scrubs the null block when a slot retires.

All are ``jax.jit(..., donate_argnums=<pool>)`` and compiled ONCE at
construction (``.trace().lower().compile()``), which buys the two
serving-latency properties the tests pin down:

- **zero allocation**: the pool is donated and every write lands in
  place (``input_output_alias`` over every pool leaf, asserted by
  ``lint_serving_engine`` at construction and in
  ``tests/test_chip_compile.py``): a decode step never copies the pool;
- **zero recompilation**: every per-request quantity is an array
  argument (tokens, temperatures, block tables, cursors, copy-on-write
  pairs) and every shape-changing knob is fixed at construction
  (``max_seqs``, the prefill buckets, the pool's size, ``top_k``), so
  admission, block growth, prefix sharing and retirement never retrace.

Capacity: a pool left to its default holds every slot's full ``max_len``
(:meth:`ServingEngine.suggest_max_seqs` says how many such slots fit a
chip); :meth:`ServingEngine.suggest_pool_blocks` sizes a smaller pool
from the compiled decode step's static memory plan
(``observability/costs.memory_budget``) — docs/SERVING.md, "How to size
the pool".
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.observability.costs import memory_budget
from apex_tpu.observability.trace import span, spans_seen
from apex_tpu.serving.cache import (NULL_BLOCK, _MIN_SCALE, PagedKVCache,
                                    BlockAllocator, KindPagedKVCache,
                                    KindBlockAllocator, AdmitPlan,
                                    LatentSpec, paged_block_bytes)
from apex_tpu.serving.sampling import sample_tokens, verify_tokens

__all__ = ["ServingEngine", "PagedServingEngine", "NO_TOKEN"]

#: what :meth:`ServingEngine.decode` and :meth:`ServingEngine.settle`
#: return in every slot when no step was in flight: no token was read
NO_TOKEN = -1


def _host(x, dtype=None) -> np.ndarray:
    """A host value marshalled for an AOT program: an engine-OWNED numpy
    copy. Owned, because dispatch is asynchronous and the caller (a
    scheduler reusing its token buffer, the allocator advancing its
    tables) mutates the source in place right after the call; numpy,
    because ``jnp.asarray`` would dispatch an eager jitted op per
    argument inside the steady-state loop."""
    return np.array(x, dtype)


def _tree_bytes(tree) -> int:
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree_util.tree_leaves(tree))


class ServingEngine:
    """See module docstring. The same AOT contract for every model: a
    slot holds ``ceil(context / block_size)`` pool blocks, the decode
    step's HBM traffic is O(actual context)
    (``paged_decode_attention``), and admissions whose prompt prefix is
    already pooled SHARE those blocks and skip prefill for the shared
    span (copy-on-write; the TTFT win ``serve/ttft_prefix_ms`` tracks).

    Host state (block tables, cursors, refcounts, the prefix-hash
    index) lives in :attr:`allocator` — a
    :class:`~apex_tpu.serving.cache.BlockAllocator` — and rides into
    the fixed-shape programs as plain array arguments, so per-request
    bookkeeping never retraces anything.

    A model built from a layer pattern (``model.cfg.cache_kinds``:
    :class:`~apex_tpu.models.pattern_decoder.PatternDecoder`) gets one
    pool and one block table per layer KIND
    (:class:`~apex_tpu.serving.cache.KindPagedKVCache`,
    :class:`~apex_tpu.serving.cache.KindBlockAllocator`): the tables and
    append targets ride into the same programs as dicts by kind, a
    window kind's blocks go back to its pool as the cursor leaves them,
    and what a ``step_stats`` model counts in a step comes back in the
    token fetch (:attr:`last_stats`). A kind that keeps a per-slot STATE
    in the place of blocks (a :class:`~apex_tpu.serving.cache.StateSpec`
    among ``cache_kinds``: Mamba-2 or Mamba-1 layers, each in the layout
    its spec gives) lives in the same cache, one
    row a slot; the prefill programs are then told the slot they fill. A
    kind that keeps latent rows (a :class:`~apex_tpu.serving.cache.
    LatentSpec`: latent attention) is a block kind whose pool holds one row
    a position for all heads. Such a model is served without the prefix
    index and without speculation (docs/SERVING.md, Limits).

    Args:
      model: a :class:`~apex_tpu.models.gpt.GPTModel` (tp=1, no SP) or
        a :class:`~apex_tpu.models.pattern_decoder.PatternDecoder`.
      params: its :meth:`init` pytree. The engine keeps the model's
        serving image of it as :attr:`params` and not the tree itself
        (:meth:`_hold_weights`).
      max_seqs: concurrent sequence slots (the decode batch width).
      max_len: per-slot capacity in tokens (<= the model's
        ``max_position_embeddings``).
      prefill_len: one prompt window, or a list of them: one AOT prefill
        program a bucket, a prompt runs the smallest that holds it and
        the scheduler admits up to the widest.
      num_blocks: pool size in blocks (block 0 is the reserved null
        block — allocatable capacity is ``num_blocks - 1``). Default:
        ``max_seqs * ceil(max_len / block_size) + 1``, every slot's
        full ``max_len`` reserved; a smaller pool serves the same slots
        at the traffic's mean length (:meth:`suggest_pool_blocks`). A
        dict by layer kind, and required, for a model with
        ``cfg.cache_kinds``.
      block_size: tokens per block; every prefill bucket is a multiple
        of it. Default: ``gcd(128, *buckets)``. The paged Pallas kernel
        takes any size on every backend; ``block_size % 128 == 0``
        keeps its score rows lane-dense on TPU.
      cache_dtype: ``jnp.bfloat16`` (default) or ``jnp.int8`` (quantized
        pool with per-(position, head) scales).
      top_k: static top-k sampling cutoff (0 = full vocab).
      quarantine: compile the poison-slot quarantine check into the
        decode program — one per-slot ``isfinite`` reduction over the
        sampling-path logits (fused into the head matmul's consumers,
        no extra memory pass) plus a ``(max_seqs,)`` poison-injection
        array argument (NaN for a slot poisons its logits — the
        deterministic :class:`~apex_tpu.elastic.faults.FaultPlan`
        injection path, zero extra compiles). After each
        :meth:`decode`, :attr:`last_finite` carries the per-slot flags
        the scheduler's quarantine reads. Default off — the decode
        program is byte-identical to a quarantine-free engine's
        (asserted in ``tests/test_resilience.py``).
      prefix_suffix_cap: longest un-shared prompt TAIL (tokens) worth
        serving through per-token decode steps on a prefix hit; a hit
        whose tail is longer falls back to the cold full prefill
        (sequential decode would beat one batched prefill only near
        full coverage). Default: ``block_size``.
      speculate_k: when > 0, compile the ``verify`` program, which
        scores each slot's last accepted token plus ``k`` drafted
        tokens in ONE pass over the cached prefix
        (:meth:`~apex_tpu.models.gpt.GPTModel.verify_forward`), runs
        the acceptance rule
        (:func:`~apex_tpu.serving.sampling.verify_tokens`) and writes
        the whole window
        (:meth:`~apex_tpu.serving.cache.PagedKVCache.append_k`). ``k``
        is the only static knob; draft tokens, temperatures and the
        active mask are array arguments, so speculative serving keeps
        the zero-recompile contract. Default 0 — the engine is
        byte-identical to a speculation-free one.
    """

    def __init__(self, model, params, *, max_seqs: int, max_len: int,
                 prefill_len: int, num_blocks: Optional[int] = None,
                 block_size: Optional[int] = None,
                 cache_dtype=jnp.bfloat16, top_k: int = 0,
                 rng_seed: int = 0, quarantine: bool = False,
                 prefix_suffix_cap: Optional[int] = None,
                 mean_context: Optional[float] = None,
                 speculate_k: int = 0):
        model._require_cacheable()
        cfg = model.cfg
        # a few prompt-length buckets, one prefill program each: a prompt
        # runs the smallest that holds it (an int is the one bucket)
        buckets = tuple(sorted({int(b) for b in (
            prefill_len if isinstance(prefill_len, (list, tuple))
            else (prefill_len,))}))
        prefill_len = buckets[-1]
        if max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"max_len {max_len} exceeds max_position_embeddings "
                f"{cfg.max_position_embeddings}")
        if prefill_len > max_len:
            raise ValueError(f"prefill_len {prefill_len} exceeds max_len "
                             f"{max_len}")
        if block_size is None:
            block_size = math.gcd(128, *buckets)
        if any(b % block_size for b in buckets):
            raise ValueError(
                f"prefill_len {buckets} must be multiples of "
                f"block_size {block_size} (the prefill program writes "
                "whole pool blocks)")
        self.by_kind = hasattr(cfg, "cache_kinds")
        if self.by_kind and (speculate_k or prefix_suffix_cap is not None):
            raise ValueError(
                "a model with pools by layer kind is served without "
                "speculation (its window kernel takes one query row; a "
                "per-slot state cannot be rolled back over rejected "
                "drafts) and without the prefix index (a window layer has "
                "handed its early blocks back; a state is no block to "
                "share): docs/SERVING.md, Limits")
        if num_blocks is None and not self.by_kind:
            # every slot's whole max_len, and the null block
            num_blocks = max_seqs * -(-max_len // block_size) + 1
        if self.by_kind != isinstance(num_blocks, dict):
            raise ValueError(
                "num_blocks is a dict by layer kind for a model with "
                "cfg.cache_kinds and one number for any other; got "
                f"{num_blocks!r}")
        self.model = model
        self.max_seqs = int(max_seqs)
        self.max_len = int(max_len)
        self.prefill_len = int(prefill_len)
        self.prefill_buckets = buckets
        self.block_size = int(block_size)
        self.num_blocks = ({k: int(n) for k, n in num_blocks.items()}
                           if self.by_kind else int(num_blocks))
        self.top_k = int(top_k)
        self.quarantine = bool(quarantine)
        self.speculate_k = int(speculate_k)
        if self.speculate_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {speculate_k}")
        if self.speculate_k + 1 > max_len:
            raise ValueError(
                f"speculate_k {speculate_k} needs a {speculate_k + 1}-token "
                f"verify window, which exceeds max_len {max_len}")
        self.prefix_suffix_cap = int(block_size if prefix_suffix_cap
                                     is None else prefix_suffix_cap)
        self.mean_context = mean_context
        self.last_finite: Optional[np.ndarray] = None
        self.last_admit: Optional[AdmitPlan] = None
        self.last_failed: list = []
        # a model with ``step_stats`` returns small int32 counters beside
        # its logits; they ride to the host IN the token fetch
        self.last_stats: Optional[np.ndarray] = None
        # the decode step in flight (its device outputs), and the slots
        # whose next input token is the one it sampled (decode())
        self._held: Optional[tuple] = None
        self._on_device = np.zeros(self.max_seqs, np.bool_)
        self.swaps = 0
        with span("engine.build"):
            self._build(model, self._hold_weights(params), cache_dtype,
                        rng_seed)

    def _hold_weights(self, params):
        """Make and keep what the AOT programs take as their weights,
        :attr:`params`: the MODEL's image of the handed tree
        (``model.serving_params``: for a
        :class:`~apex_tpu.models.gpt.GPTModel` with float32 weights the
        layers' matrices in the compute dtype, the tables float32 for
        the lookup and a compute-dtype copy of the word table for the
        tied head), made by a cast program that is compiled here and run
        again by every :meth:`swap_params`. The programs then read the
        image as it lies; handed the float32 tree they would round all
        of it on every run. Where the model has no such method, or its
        image of ``params`` is ``params`` (every matrix in the dtype of
        its use), the engine holds the very arrays it was handed.

        The handed tree itself is NOT kept, only its shapes and dtypes
        (:attr:`params_spec`: what a swap, or a checkpoint restored for
        one, has to look like): whoever frees the engine's weights
        (``del`` of the leaves of :attr:`params`) has freed all of them.
        Returns :attr:`params`."""
        self.params_spec = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), params)
        self._image_compiled = None
        to_image = getattr(self.model, "serving_params", None)
        if to_image is not None and \
                jax.eval_shape(to_image, self.params_spec) != self.params_spec:
            with span("compile.image"):
                self._image_compiled = jax.jit(to_image).lower(
                    self.params_spec).compile()
        self.params = self._image(params)
        self.weights_handed_bytes = _tree_bytes(self.params_spec)
        self.weights_held_bytes = _tree_bytes(self.params)
        return self.params

    def _image(self, params):
        """``params`` (of :attr:`params_spec`) as the programs take them."""
        if self._image_compiled is None:
            return params
        return self._image_compiled(params)

    def _build(self, model, params, cache_dtype, rng_seed: int) -> None:
        """The pool, its allocator and the AOT programs (``__init__``
        past its checks)."""
        cfg = model.cfg
        max_seqs, num_blocks = self.max_seqs, self.num_blocks
        block_size = self.block_size
        self.prefill_blocks = self.prefill_len // self.block_size
        blocks_per_slot = -(-self.max_len // self.block_size)
        if self.by_kind:
            self.cache = KindPagedKVCache.create(
                cfg.cache_kinds, num_blocks, cfg.num_key_value_heads,
                block_size, cfg.head_dim, dtype=cache_dtype,
                max_seqs=max_seqs)
            self.allocator = KindBlockAllocator(
                cfg.cache_kinds, num_blocks, block_size, blocks_per_slot,
                max_seqs)
        else:
            self.cache = PagedKVCache.create(
                cfg.num_layers, num_blocks, cfg.num_attention_heads,
                block_size, cfg.head_dim, dtype=cache_dtype)
            self.allocator = BlockAllocator(num_blocks, block_size,
                                            blocks_per_slot, max_seqs)
        #: a layer kind keeps a per-slot state (cache.py, "A state kind"):
        #: the prefill programs take the slot they fill
        self.has_state = self.by_kind and self.allocator.has_state
        #: a layer kind keeps latent rows (cache.py, "A latent kind"): the
        #: decode kernel reads every served slot's rows, its own included;
        #: :attr:`latent_positions_read` counts them, step by step
        self.latent = self.by_kind and any(
            isinstance(spec, LatentSpec) for spec in cfg.cache_kinds.values())
        self.latent_positions_read = 0
        #: the positions YaRN's table stretches (0: the model has none): a
        #: prompt position at or past it is rotated by the stretched table
        self.rope_original = int(cfg.rope_original) \
            if getattr(cfg, "rope_factor", 1.0) > 1.0 else 0
        stats = getattr(model, "step_stats", False)
        self._stats_shape = model.stats_shape if stats else None
        #: the counter each column of :attr:`last_stats` adds to
        self.stats_names = model.stats_names if stats else ()

        def packed(toks, out):
            """The sampled tokens with the model's counters behind them:
            one array, one fetch."""
            if not stats:
                return toks
            return jnp.concatenate([toks.reshape(-1).astype(jnp.int32),
                                    out[2].reshape(-1).astype(jnp.int32)])

        def prefill_step(params, cache, tokens, block_row, true_len,
                         temperature, rng, *slot):
            with jax.named_scope("serve_prefill"):
                out = model.forward(params, tokens, kv_cache=cache,
                                    block_row=block_row,
                                    prompt_len=true_len,
                                    last_logit_only=True,
                                    **({"slot": slot[0]} if slot else {}))
                logits, cache = out[0], out[1]
                tok = sample_tokens(logits[0], rng, temperature[None],
                                    self.top_k)[0]
            return cache, packed(tok, out)

        mc = self.mean_context

        def _decode_core(params, cache, tables, lengths, tokens, prev,
                         fresh, temperature, block_ids, offsets, cow_src,
                         cow_dst, rng, poison=None):
            # a slot's input token: the host's where only the host knows
            # it (the first step after its prefill), else the one the
            # step before sampled, never read back to the host for this
            tokens = jnp.where(fresh, tokens, prev[:tokens.shape[0]])
            out = model.forward(
                params, tokens[:, None], kv_cache=cache,
                block_tables=tables, lengths=lengths,
                append_block_ids=block_ids, append_offsets=offsets,
                cow_src=cow_src, cow_dst=cow_dst, mean_context=mc)
            logits, cache = out[0], out[1]
            if poison is not None:
                logits = logits + poison[:, None]
                finite = jnp.all(jnp.isfinite(logits), axis=-1)
                toks = sample_tokens(logits, rng, temperature,
                                     self.top_k)
                return cache, packed(toks, out), finite
            toks = sample_tokens(logits, rng, temperature, self.top_k)
            return cache, packed(toks, out)

        if self.quarantine:
            def decode_step(params, cache, tables, lengths, tokens, prev,
                            fresh, temperature, block_ids, offsets,
                            cow_src, cow_dst, rng, poison):
                with jax.named_scope("serve_decode"):
                    return _decode_core(params, cache, tables, lengths,
                                        tokens, prev, fresh, temperature,
                                        block_ids, offsets, cow_src,
                                        cow_dst, rng, poison)
        else:
            def decode_step(params, cache, tables, lengths, tokens, prev,
                            fresh, temperature, block_ids, offsets,
                            cow_src, cow_dst, rng):
                with jax.named_scope("serve_decode"):
                    return _decode_core(params, cache, tables, lengths,
                                        tokens, prev, fresh, temperature,
                                        block_ids, offsets, cow_src,
                                        cow_dst, rng)

        self._init_key(rng_seed)
        S = self.max_seqs
        # a dict by BLOCK kind (a state kind has no table and no blocks)
        by_kind = (lambda x: {k: x for k in self.allocator.kinds}) \
            if self.by_kind else (lambda x: x)
        ex_scalar = jnp.zeros((), jnp.int32)
        ex_temp = jnp.zeros((), jnp.float32)
        self._prefill_programs = {}
        with span("compile.prefill"):
            for bucket in self.prefill_buckets:
                self.prefill_traced = jax.jit(
                    prefill_step, donate_argnums=(1,)).trace(
                        params, self.cache,
                        jnp.zeros((1, bucket), jnp.int32),
                        by_kind(jnp.zeros((bucket // block_size,),
                                          jnp.int32)),
                        ex_scalar, ex_temp, self._key,
                        *((ex_scalar,) if self.has_state else ()))
                self._prefill_programs[bucket] = \
                    self.prefill_traced.lower().compile()
            # the widest bucket's program stands for the leg (lint,
            # attention_paths)
            self.prefill_compiled = self._prefill_programs[self.prefill_len]
        self._zero_poison = jnp.zeros((S,), jnp.float32)
        zs = jnp.zeros((S,), jnp.int32)
        # what a decode step returns, and the next one takes as ``prev``:
        # the tokens, and a step_stats model's counts behind them
        self._no_prev = jnp.zeros(
            (S + (math.prod(self._stats_shape) if stats else 0),),
            jnp.int32)
        decode_args = (params, self.cache,
                       by_kind(jnp.zeros((S, blocks_per_slot), jnp.int32)),
                       zs, zs, self._no_prev, jnp.ones((S,), jnp.bool_),
                       jnp.zeros((S,), jnp.float32), by_kind(zs),
                       zs, zs, zs, self._key)
        if self.quarantine:
            decode_args += (self._zero_poison,)
        with span("compile.decode"):
            self.decode_traced = jax.jit(
                decode_step, donate_argnums=(1,)).trace(*decode_args)
            self.decode_compiled = self.decode_traced.lower().compile()

        self.verify_traced = None
        self.verify_compiled = None
        if self.speculate_k > 0:
            K = self.speculate_k

            def _verify_core(params, cache, tables, lengths, tokens,
                             drafts, temperature, active, block_ids,
                             offsets, cow_src, cow_dst, rng, poison=None):
                # COW resolution happens inside verify_forward (before
                # any read), exactly like the decode leg, and so does
                # the append, layer by layer: it targets every row of
                # the window — rejected rows land in blocks above the
                # host cursor mirror, which only ever advances by the
                # accepted count
                logits, cache = model.verify_forward(
                    params, tokens, cache, block_tables=tables,
                    lengths=lengths, append_block_ids=block_ids,
                    append_offsets=offsets, cow_src=cow_src,
                    cow_dst=cow_dst, mean_context=mc)
                finite = None
                if poison is not None:
                    logits = logits + poison[:, None, None]
                    finite = jnp.all(jnp.isfinite(logits), axis=(-2, -1))
                toks, accepted = verify_tokens(logits, drafts, rng,
                                               temperature, self.top_k)
                counts = jnp.where(active, accepted + 1, 0)
                if finite is not None:
                    return cache, toks, counts, finite
                return cache, toks, counts

            if self.quarantine:
                def verify_step(params, cache, tables, lengths, tokens,
                                drafts, temperature, active, block_ids,
                                offsets, cow_src, cow_dst, rng, poison):
                    with jax.named_scope("serve_verify"):
                        return _verify_core(params, cache, tables,
                                            lengths, tokens, drafts,
                                            temperature, active,
                                            block_ids, offsets, cow_src,
                                            cow_dst, rng, poison)
            else:
                def verify_step(params, cache, tables, lengths, tokens,
                                drafts, temperature, active, block_ids,
                                offsets, cow_src, cow_dst, rng):
                    with jax.named_scope("serve_verify"):
                        return _verify_core(params, cache, tables,
                                            lengths, tokens, drafts,
                                            temperature, active,
                                            block_ids, offsets, cow_src,
                                            cow_dst, rng)

            zq = jnp.zeros((S, K + 1), jnp.int32)
            verify_args = (params, self.cache,
                           jnp.zeros((S, blocks_per_slot), jnp.int32),
                           zs, zq, jnp.zeros((S, K), jnp.int32),
                           jnp.zeros((S,), jnp.float32),
                           jnp.ones((S,), jnp.bool_), zq, zq, zs, zs,
                           self._key)
            if self.quarantine:
                verify_args += (self._zero_poison,)
            with span("compile.verify"):
                self.verify_traced = jax.jit(
                    verify_step, donate_argnums=(1,)).trace(*verify_args)
                self.verify_compiled = self.verify_traced.lower().compile()

        def release_step(cache):
            # re-zero the reserved null block: every masked write
            # (inactive slot, saturated slot, prompt padding) lands in
            # it, so a retire is the natural point to scrub the garbage
            # back to the "reads as zeros" invariant. Real in-place
            # writes on every donated leaf — the donation lint holds.
            if self.by_kind:
                return cache.scrub_null_blocks()
            new = {"k": cache.k.at[:, NULL_BLOCK].set(0),
                   "v": cache.v.at[:, NULL_BLOCK].set(0)}
            if cache.quantized:
                new["k_scale"] = cache.k_scale.at[:, NULL_BLOCK].set(
                    jnp.float32(_MIN_SCALE))
                new["v_scale"] = cache.v_scale.at[:, NULL_BLOCK].set(
                    jnp.float32(_MIN_SCALE))
            return dataclasses.replace(cache, **new)

        with span("compile.release"):
            self.release_compiled = jax.jit(
                release_step, donate_argnums=(0,)).trace(
                    self.cache).lower().compile()

        from apex_tpu.analysis.program import (lint_serving_engine,
                                               verify_findings)
        with span("engine.lint"):
            verify_findings(lint_serving_engine(self),
                            "ServingEngine construction")

    # -- the sampling key ---------------------------------------------------

    def _init_key(self, rng_seed: int) -> None:
        self._key, _ = jax.random.split(jax.random.PRNGKey(rng_seed))
        # the per-dispatch key split is an AOT program like the steps:
        # the steady-state loop dispatches ONLY compiled executables and
        # marshals host values with numpy. An eager jnp op there
        # (``jax.random.split``, ``jnp.asarray(x, dtype)``) is a jitted
        # primitive that can fall off jit's C++ fast path and then
        # reports a trace on every dispatch (docs/SERVING.md
        # "Zero-recompile contract").
        self._split_compiled = jax.jit(
            lambda key: tuple(jax.random.split(key))).lower(
                self._key).compile()

    def _next_key(self) -> jax.Array:
        self._key, sub = self._split_compiled(self._key)
        return sub

    # -- admission ----------------------------------------------------------

    def can_admit(self, prompt: Sequence[int]) -> bool:
        """Whether the pool can take ``prompt`` right now (conservative:
        assumes a cold admission; a prefix hit needs fewer blocks)."""
        return self.allocator.can_admit(len(prompt))

    def bucket_of(self, tokens: int) -> int:
        """The smallest prefill bucket that holds a prompt of ``tokens``:
        the rows its prefill program computes, padding included."""
        if tokens == 0:
            raise ValueError("empty prompt")
        if tokens > self.prefill_len:
            raise ValueError(
                f"prompt length {tokens} exceeds the prefill window "
                f"{self.prefill_len} (pick a larger prefill_len at "
                "engine construction)")
        return next(b for b in self.prefill_buckets if b >= tokens)

    def pad_prompt(self, prompt: Sequence[int]) -> np.ndarray:
        """``prompt`` right-padded to the smallest bucket that holds it."""
        bucket = self.bucket_of(len(prompt))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, : len(prompt)] = np.asarray(prompt, np.int32)
        return padded

    def _unpack(self, fetched: np.ndarray, n: int) -> np.ndarray:
        """The first ``n`` values of a token fetch; what a ``step_stats``
        model packed behind them goes to :attr:`last_stats`."""
        if self._stats_shape is None:
            return fetched
        self.last_stats = fetched[n:].reshape(self._stats_shape)
        return fetched[:n]

    def prefill(self, prompt: Sequence[int], slot: int,
                temperature: float = 0.0) -> int:
        """Admit ``prompt`` into ``slot`` and return the first sampled
        token. Two paths, chosen by the allocator's prefix index:

        - **cold**: allocate blocks, run the batched prefill program.
        - **prefix hit** (tail within ``prefix_suffix_cap``): map the
          shared blocks (refcount++), skip prefill for the shared span,
          and feed ONLY the un-shared tail through the decode program
          one token at a time (``active`` = this slot alone — other
          slots' cursors and blocks are untouched). The final step's
          sample is the first token.

        Raises :class:`~apex_tpu.serving.cache.PoolExhausted` when the
        blocks aren't there — the scheduler queues on that (typed
        :class:`~apex_tpu.serving.resilience.Rejection` at submit).
        Sets :attr:`last_admit` to the chosen
        :class:`~apex_tpu.serving.cache.AdmitPlan` for the scheduler's
        prefix metrics."""
        if not 0 <= int(slot) < self.max_seqs:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.max_seqs})")
        # the slot's next token is this prompt's, known on the host
        self._on_device[slot] = False
        with span("engine.prefill", slot=int(slot)):
            with span("prefill.plan"):
                prompt = [int(t) for t in prompt]
                shared = self.allocator.lookup(prompt)
                covered = min(len(shared) * self.block_size,
                              len(prompt) - 1)
                if shared and len(prompt) - covered > self.prefix_suffix_cap:
                    shared = []        # tail too long: cold prefill wins
                padded = self.pad_prompt(prompt)
                bucket = padded.shape[1]
                plan = self.allocator.admit(slot, prompt,
                                            bucket // self.block_size,
                                            share=bool(shared))
                self.last_admit = plan
                if plan.prefill:
                    args = (self.params, self.cache, padded,
                            jax.tree_util.tree_map(
                                lambda r: _host(r, np.int32),
                                plan.block_row,
                                is_leaf=lambda r: isinstance(r, list)),
                            _host(len(prompt), np.int32),
                            _host(temperature, np.float32),
                            self._next_key())
                    if self.has_state:
                        args += (_host(slot, np.int32),)
            if plan.prefill:
                # with several buckets the span says which one's program
                # the prompt ran and how many of its positions are tokens
                # (the rest is padding)
                ids = dict(bucket=bucket, tokens=len(prompt)) \
                    if len(self.prefill_buckets) > 1 else {}
                with span("prefill.dispatch", **ids):
                    self.cache, tok = self._prefill_programs[bucket](*args)
                with span("prefill.index"):
                    # index the freshly written full blocks so LATER
                    # admissions can share them
                    self.allocator.register_prefix(slot, prompt)
                self._drain()
                with span("prefill.wait"):
                    return int(self._unpack(np.asarray(tok).reshape(-1),
                                            1)[0])
            # prefix hit: decode the un-shared tail token by token through
            # the ordinary decode program (same compiled program — zero
            # recompiles), other slots frozen, each step read at once and
            # the step in flight left as it is; each is an engine.decode
            # span inside this one
            self._drain()
            active = np.zeros(self.max_seqs, np.bool_)
            active[slot] = True
            tokens = np.zeros(self.max_seqs, np.int32)
            temps = np.zeros(self.max_seqs, np.float32)
            temps[slot] = temperature
            tok = 0
            for t in plan.suffix:
                tokens[slot] = t
                toks = self._decode(tokens, temps, active, None, hold=False)
                tok = int(toks[slot])
            return tok

    def _drain(self) -> None:
        """Wait, where a decode step is in flight, until the device has
        computed it (nothing is fetched: its tokens stay held for the next
        :meth:`decode` or :meth:`settle`). A prefill's program is queued
        behind that step, so this is the prefill's wait for it, timed
        apart from the prefill's own."""
        if self._held is not None:
            with span("prefill.drain"):
                jax.block_until_ready(self._held[0])

    # -- stepping -----------------------------------------------------------

    def decode(self, tokens: np.ndarray, temperatures: np.ndarray,
               active: Optional[np.ndarray] = None,
               poison: Optional[np.ndarray] = None) -> np.ndarray:
        """Dispatch one decode step for every slot, THEN read the step
        before it: returns the previous call's next token per slot, or
        :data:`NO_TOKEN` in every slot when no step was in flight (an
        array either way, so a caller that indexes the result need not
        branch). One step is always in flight
        between calls, so the device runs this step while the host reads
        the last one and plans the next (:meth:`settle` reads the one in
        flight and holds none).

        ``tokens (max_seqs,)`` is the last emitted token per slot, read
        only where the host alone knows it: a slot outside the previous
        call's ``active`` mask, or prefilled (or released) since. Every
        other slot's input is the token the previous step sampled, taken
        on the device. ``active`` (``(max_seqs,)`` bool,
        default all): slots outside it keep a frozen cursor and write
        nothing. Consumes and replaces the donated pool.

        ``poison`` (quarantine engines only, ``(max_seqs,)`` f32,
        default zeros) is added to each slot's sampling-path logits —
        the deterministic fault-injection argument. On a quarantine
        engine :attr:`last_finite` holds the per-slot finite flags of
        the step whose tokens were returned (and :attr:`last_stats` its
        counts); on a plain engine it stays None (and a poison array is
        refused — the fault would be silently dropped).

        Per-step block bookkeeping happens HERE, for the step
        dispatched: pending copy-on-writes are resolved (the device
        copies the block before writing it), cursors that crossed a
        block boundary get a fresh block, and slots the exhausted pool
        could not serve land in :attr:`last_failed` — their append is
        dropped (null block) and the scheduler retires them loudly.

        Device order makes the step in flight safe: whatever the host
        does to a slot after this call (retire it, hand its blocks or
        its state row to another prompt) reaches the device as a later
        program, so it lands after this step's writes."""
        return self._decode(tokens, temperatures, active, poison, hold=True)

    def _decode(self, tokens, temperatures, active, poison, *, hold: bool):
        """:meth:`decode`; ``hold=False`` reads the step at once and
        leaves the step in flight as it is (the prefix tail's steps)."""
        if active is None:
            active = np.ones(self.max_seqs, np.bool_)
        active = np.asarray(active, bool)
        self._refuse_poison_unless_quarantine(poison)
        before = self._held if hold else None
        # the walk only fills the span's keywords: skipped where no
        # recording or profiler session takes them
        walk = {}
        if spans_seen():
            walk["live_blocks"], walk["table_blocks"] = \
                self.allocator.walk_blocks()
        with span("engine.decode", active=int(np.count_nonzero(active)),
                  inflight=int(before is not None), **walk):
            with span("decode.plan"):
                step = self.allocator.prepare_step(
                    list(np.flatnonzero(active)))
                self.last_failed = list(step.failed)
                ok = active.copy()
                ok[step.failed] = False
                block_ids, offsets = self.allocator.append_targets(ok)
                prev = self._no_prev if self._held is None \
                    else self._held[0]
                # a dict by layer kind where the pools are (a leaf is an
                # array either way)
                host = lambda x: jax.tree_util.tree_map(_host, x)
                args = (self.params, self.cache,
                        host(self.allocator.tables),
                        _host(self.allocator.lengths),
                        _host(tokens, np.int32), prev,
                        _host(~self._on_device),
                        _host(temperatures, np.float32),
                        host(block_ids), _host(offsets),
                        _host(step.cow_src), _host(step.cow_dst),
                        self._next_key())
                args += self._poison_arg(poison)
            with span("decode.dispatch"):
                self.cache, toks, *finite = self.decode_compiled(*args)
            with span("decode.advance"):
                if self.latent:
                    # a slot whose append was given a block reads its
                    # cursor's rows and its own (the program's ``reads``)
                    served = next(iter(block_ids.values())) != NULL_BLOCK
                    self.latent_positions_read += int(np.sum(
                        self.allocator.lengths[served].astype(np.int64) + 1))
                self.allocator.advance(list(np.flatnonzero(ok)))
            if not hold:
                return self._read((toks, finite))
            self._held, self._on_device = (toks, finite), active.copy()
            return self._read(before)

    def _read(self, held) -> np.ndarray:
        """The tokens of a dispatched step, fetched (:data:`NO_TOKEN` in
        every slot for none); :attr:`last_finite` and :attr:`last_stats`
        become its."""
        if held is None:
            return np.full(self.max_seqs, NO_TOKEN, np.int32)
        toks, finite = held
        with span("decode.wait"):
            if finite:
                self.last_finite = np.asarray(finite[0])
            return self._unpack(np.asarray(toks), self.max_seqs)

    def settle(self) -> np.ndarray:
        """Read the step in flight and hold none: its tokens as the next
        :meth:`decode` would have returned them (:data:`NO_TOKEN` in
        every slot when no step was in flight). That next decode then
        takes every slot's token from the host."""
        held, self._held = self._held, None
        self._on_device[:] = False
        return self._read(held)

    def _refuse_poison_unless_quarantine(self, poison) -> None:
        if poison is not None and not self.quarantine:
            raise ValueError(
                "poison injection requires a quarantine engine "
                f"({type(self).__name__}(..., quarantine=True)) — on a "
                "plain engine the fault would be silently dropped")

    def _poison_arg(self, poison) -> tuple:
        """The quarantine programs' trailing ``poison`` argument."""
        if not self.quarantine:
            return ()
        return (self._zero_poison if poison is None
                else _host(poison, np.float32),)

    def verify(self, tokens: np.ndarray, drafts: np.ndarray,
               temperatures: np.ndarray,
               active: Optional[np.ndarray] = None,
               poison: Optional[np.ndarray] = None):
        """One speculative verify step for every slot: ``tokens
        (max_seqs,)`` are each slot's last emitted token, ``drafts
        (max_seqs, speculate_k)`` the draft-source proposals after it.
        Returns ``(out_tokens (max_seqs, speculate_k + 1), counts
        (max_seqs,))`` — slot ``s`` emits ``out_tokens[s, :counts[s]]``
        this step (``counts`` is 0 for inactive slots, otherwise
        ``accepted_drafts + 1``), and its cursor has already advanced by
        exactly ``counts[s]``: rejected rows sit above the cursor where
        no read masks them in, so retiring the slot at ANY point leaves
        no drafted-but-rejected KV visible. Consumes and replaces the
        donated pool; requires ``speculate_k > 0`` at construction.
        ``poison`` follows the :meth:`decode` quarantine contract (the
        finite flags are of the VERIFY logits).

        Per-window block bookkeeping happens HERE: :meth:`~apex_tpu.serving.cache.BlockAllocator.
        prepare_verify` makes every block the ``speculate_k + 1``-token
        window touches slot-private and writable (COW resolved, fresh
        blocks mapped, atomic per slot), slots the exhausted pool could
        not fully serve land in :attr:`last_failed` (their window aims
        at the null block and their count comes back 0 — the scheduler
        retires them loudly), and the cursor mirror advances by each
        surviving slot's ACCEPTED count only."""
        if self.verify_compiled is None:
            raise ValueError(
                "verify requires a speculative engine "
                f"({type(self).__name__}(..., speculate_k=k) with k > 0)")
        Q = self.speculate_k + 1
        if active is None:
            active = np.ones(self.max_seqs, np.bool_)
        active = np.asarray(active, bool)
        self._refuse_poison_unless_quarantine(poison)
        with span("engine.verify", active=int(np.count_nonzero(active))):
            with span("verify.plan"):
                step = self.allocator.prepare_verify(
                    list(np.flatnonzero(active)), Q)
                self.last_failed = list(step.failed)
                ok = active.copy()
                ok[step.failed] = False
                block_ids, offsets = self.allocator.verify_targets(ok, Q)
                drafts = np.asarray(drafts, np.int32).reshape(
                    self.max_seqs, self.speculate_k)
                tok_mat = np.concatenate(
                    [np.asarray(tokens, np.int32).reshape(self.max_seqs, 1),
                     drafts], axis=1)
                args = (self.params, self.cache,
                        _host(self.allocator.tables),
                        _host(self.allocator.lengths),
                        _host(tok_mat), _host(drafts),
                        _host(temperatures, np.float32),
                        _host(ok), _host(block_ids),
                        _host(offsets), _host(step.cow_src),
                        _host(step.cow_dst), self._next_key())
                args += self._poison_arg(poison)
            with span("verify.dispatch"):
                self.cache, toks, counts, *finite = self.verify_compiled(
                    *args)
            with span("verify.wait"):
                # the advance needs the accepted counts, so here it
                # cannot hide under the device's work as decode's does
                if finite:
                    self.last_finite = np.asarray(finite[0])
                toks, counts = np.asarray(toks), np.asarray(counts)
            with span("verify.advance"):
                okidx = np.flatnonzero(ok)
                self.allocator.advance_counts(
                    list(okidx), [int(counts[s]) for s in okidx])
            return toks, counts

    def release_slot(self, slot: int) -> None:
        """Retire ``slot``: drop its block references on the host
        (shared blocks survive for their other readers — and for the
        prefix cache) and scrub the null block on device."""
        if not 0 <= int(slot) < self.max_seqs:
            raise ValueError(f"slot {slot} out of range "
                             f"[0, {self.max_seqs})")
        self._on_device[slot] = False
        with span("engine.release", slot=int(slot)):
            self.allocator.release(slot)
            self.cache = self.release_compiled(self.cache)

    # -- hot weight swap ----------------------------------------------------

    def swap_params(self, new_params, *, relint: bool = True) -> None:
        """Swap the serving weights in place with ZERO recompiles.

        The weights are a plain (non-donated) array argument of all the
        AOT programs, so replacing :attr:`params` retargets every
        subsequent prefill/decode/release dispatch at the new weights —
        no retrace, no recompile, no cache reallocation (the
        compile-storm counters stay flat; asserted under
        ``recompile_guard`` in ``tests/test_resilience.py``). In-flight
        sequences keep their OLD-weight KV prefix and extend it under
        the new weights — the standard serve-while-train rollover
        semantics; drain first
        (:meth:`~apex_tpu.serving.scheduler.SlotScheduler.drain`) for a
        clean generation boundary.

        A swap takes what construction took: ``new_params`` must match
        :attr:`params_spec` exactly (same treedef, same leaf
        shapes/dtypes — the trainer's tree, not the image the programs
        read). Anything else is refused here at the host boundary,
        before a leaf is cast: it would retrace on next dispatch, which
        is exactly the compile storm this method exists to avoid. The
        image is then made by the cast program construction compiled
        (:meth:`_hold_weights`), and ``new_params`` is not kept.
        ``relint=True`` re-runs the analysis engine's donation/aliasing
        lint over the compiled programs after the swap (rule
        ``jaxpr-donation`` — the construction-time self-check repeated
        at every rollover).
        """
        old_leaves, old_def = jax.tree_util.tree_flatten(self.params_spec)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_params)
        if old_def != new_def:
            raise ValueError(
                "swap_params: new params tree structure differs from "
                "what the engine was built on — a swap must never "
                f"retrace (old {old_def}, new {new_def})")
        converted = []
        for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
            # one device_put per leaf: validate on the converted array
            # and keep it, rather than transferring the model twice
            n = jnp.asarray(n)
            if o.shape != n.shape or o.dtype != n.dtype:
                raise ValueError(
                    f"swap_params: leaf {i} is {n.shape}/{n.dtype}, "
                    f"built on {o.shape}/{o.dtype} — a swap must "
                    "never retrace")
            converted.append(n)
        self.params = self._image(
            jax.tree_util.tree_unflatten(new_def, converted))
        self.swaps += 1
        if relint:
            from apex_tpu.analysis.program import (lint_serving_engine,
                                                   verify_findings)
            verify_findings(lint_serving_engine(self),
                            "ServingEngine.swap_params")

    # -- what the compiler was handed ----------------------------------------

    def attention_paths(self) -> Dict[str, str]:
        """Which attention path each AOT program took, read off its
        compiled text: ``"pallas"`` when the program holds a Mosaic
        kernel (``tpu_custom_call``), ``"xla"`` when attention lowered
        to plain XLA ops (``use_flash=False``, or a shape the
        ``use_pallas=None`` gate sent to the reference path). Keys:
        ``prefill``, ``decode`` and, on a speculative engine,
        ``verify``. Meaningful on the TPU backend — on CPU the kernels
        run interpreted, which inlines them as plain ops, so every
        program reads ``"xla"`` there."""
        programs = {"prefill": self.prefill_compiled,
                    "decode": self.decode_compiled}
        if self.verify_compiled is not None:
            programs["verify"] = self.verify_compiled
        return {name: "pallas" if "tpu_custom_call" in prog.as_text()
                else "xla" for name, prog in programs.items()}

    # -- capacity -----------------------------------------------------------

    def block_bytes(self) -> int:
        """HBM bytes of one pool block (K and V, all layers)."""
        cfg = self.model.cfg
        if self.by_kind:
            raise NotImplementedError(
                "the capacity arithmetic sizes one pool; pools by layer "
                "kind are sized by the caller (num_blocks by kind)")
        return paged_block_bytes(cfg.num_layers, cfg.num_attention_heads,
                                 self.block_size, cfg.head_dim,
                                 self.cache.k.dtype)

    def bytes_per_slot(self) -> int:
        """HBM bytes of one slot's full-length reservation: the
        ``ceil(max_len / block_size)`` blocks the default pool keeps for
        every slot."""
        return self.allocator.blocks_per_slot * self.block_bytes()

    def overhead_bytes(self) -> Optional[int]:
        """Non-pool HBM the compiled decode step pins (params, logits,
        temporaries), from the executable's static memory plan — None
        when the backend reports no analysis."""
        budget = memory_budget(self.decode_compiled)
        if budget is None:
            return None
        return max(0, int(budget["peak_hbm_bytes"]) - self.cache.nbytes())

    def _pool_budget(self, hbm_bytes: int, reserve_fraction: float) -> int:
        """What of ``hbm_bytes`` is left for the pool: the compiled
        step's non-pool footprint (measured; the held weights where the
        backend exposes no memory analysis) subtracted and a
        ``reserve_fraction`` margin held back."""
        overhead = self.overhead_bytes()
        if overhead is None:
            overhead = self.weights_held_bytes
        return int(hbm_bytes * (1.0 - reserve_fraction)) - overhead

    def suggest_max_seqs(self, hbm_bytes: int,
                         reserve_fraction: float = 0.1) -> int:
        """Max concurrent slots whose full ``max_len`` reservation fits
        ``hbm_bytes`` — the ``max_seqs`` to build a default-sized pool
        with (:meth:`_pool_budget` over :meth:`bytes_per_slot`)."""
        return max(0, self._pool_budget(hbm_bytes, reserve_fraction)
                   // self.bytes_per_slot())

    def suggest_pool_blocks(self, hbm_bytes: int, mean_len: float,
                            reserve_fraction: float = 0.1) -> int:
        """Pool blocks that fit ``hbm_bytes`` (:meth:`_pool_budget` over
        :meth:`block_bytes`). The mean-length capacity math reads off
        it: a pool of ``B`` blocks sustains about ``B * block_size /
        mean_len`` concurrent sequences — against the ``HBM / (max_len
        bytes-per-slot)`` of :meth:`suggest_max_seqs`, a ``max_len /
        mean_len`` capacity win at the same HBM."""
        if mean_len <= 0:
            raise ValueError(f"mean_len must be positive, got {mean_len}")
        return max(0, self._pool_budget(hbm_bytes, reserve_fraction)
                   // self.block_bytes())

    def suggest_max_seqs_for_pool(self, num_blocks: int,
                                  mean_len: float) -> int:
        """Concurrent sequences a ``num_blocks`` pool sustains at the
        observed ``mean_len`` (the second half of the capacity math)."""
        per_seq = max(1, -(-int(mean_len) // self.block_size))
        return max(0, (num_blocks - 1) // per_seq)


# the name benchmark/ imports, which only a benchmark PR may change
PagedServingEngine = ServingEngine

"""Benchmarks against BASELINE.md's measurable configs.

Default run executes EVERY config — one JSON line each, the headline
LAST (so a driver that keeps the final line gets the headline) — and
also writes the full set to ``BENCH_CONFIGS.json``. ``--headline``
runs only the headline.

Headline: ImageNet ResNet-50 train-step throughput per chip, amp O2
semantics (bf16 compute / fp32 master params, BN stats fp32 with
compute-dtype apply — see docs/PERF.md), FusedSGD momentum inside a
``FlatOptimizer`` (the ``multi_tensor_apply`` tier —
``reference:apex/multi_tensor_apply/multi_tensor_apply.py:28-34``;
round-5 A/B in docs/PERF.md shows this wrap beats both per-leaf and
persistent-flat *inside the donated step*), synthetic data (the
reference's ``--prof`` style synthetic path).

``vs_baseline`` compares against NVIDIA's published DGX-A100
DeepLearningExamples ResNet-50 AMP number (~2470 imgs/sec per A100), the
"8xA100 amp-O2+DDP" north-star divided per chip; the reference repo itself
publishes no numbers (BASELINE.md). The line also carries ``mfu``
(model-flops-utilization from XLA's compiled cost analysis over the chip's
peak bf16 throughput), ``std_ms``, and ``step_ms``. Every headline/GPT
line additionally carries ``modeled_step_ms`` (the pyprof per-region
roofline lower bound of the exact program measured — the denominator
"how fast could this step possibly run") and ``comm_exposed_ms``
(modeled collective traffic the measured step failed to hide under
compute; 0.0 on single-chip programs) — see docs/OBSERVABILITY.md
"Step-time attribution" and ``scripts/attribute_step.py`` for the full
per-region breakdown.

Other configs:
  config 2 — FusedLayerNorm fwd+bwd, the library's auto-selected path
             (measured: XLA at every hidden size) vs forced-Pallas at a
             transformer shape and a large-hidden (32k) point
             (``reference:apex/normalization/fused_layer_norm.py:168-201``,
             ``reference:apex/contrib/csrc/layer_norm/ln_api.cpp:246``);
  config 3 — FusedAdam step time, per-leaf vs FlatOptimizer flat-buffer
             (``reference:apex/optimizers/fused_adam.py:90``);
  config 5 — GPT-small train step (Mosaic-compiled flash attention,
             vocab-parallel-shape loss) tokens/sec
             (``reference:apex/transformer/testing/standalone_gpt.py:1440``);
             anchored to 40% MFU — the published llm.c/nanoGPT-class
             utilization for GPT-2-124M-scale A100 training — over this
             chip's peak, using the compiled step's exact FLOP count;
  remat    — GPT-small train step swept over the activation-remat
             policies (none|selective|full|offload, apex_tpu/remat.py):
             ``gpt_remat_<policy>_step_ms`` + ``_temp_bytes`` trace the
             memory/compute frontier (docs/PERF.md "Remat & HBM");
  flash    — flash-attention seq-4096 fwd+bwd vs XLA attention;
  dp_ovl   — gradient-accumulation window + FusedAdam on the full DP
             mesh, bucketed end-of-window sync vs monolithic per-leaf
             psums (``dp_window_overlap_step_ms``; needs >= 2 devices,
             CPU ratio ~1.0 expected — docs/PERF.md "DP overlap + ZeRO");
  sp_ovl   — GPT-small TP=2 sequence-parallel fwd+bwd, ring-decomposed
             collective matmuls vs the fused all_gather/psum_scatter
             baseline (``gpt_sp_overlap_tokens_per_sec``; needs >= 2
             devices, emits a skip line otherwise — docs/PERF.md
             "Dependent-collective overlap");
  paged    — serving fast path: ``gpt_decode_tok_per_sec_paged`` (the
             saturating grid through the AOT ``ServingEngine`` —
             block-pool cache, bounded-grid Pallas decode kernel,
             fixed-shape sampling; ``vs_baseline`` is measured over the
             HBM roofline) and
             ``gpt_decode_ttft_prefix_ms`` (shared-prefix admission vs
             the cold prefill it skips); engine config is the
             declarative ``BENCH_DECODE_CONFIGS`` table
             (docs/SERVING.md "Paged serving");
  spec     — speculative decoding: ``gpt_decode_tok_per_sec_spec``, a
             same-session A/B of the scheduler loop with and without
             ``speculate_k`` drafting on a repetitive-text workload
             (acceptance rate on the line; docs/SERVING.md
             "Speculative decoding");
  fast     — the compound ``fastpath`` preset (tp_comm_overlap +
             bucketed DP + ZeRO-1 backward-interleaved apply +
             selective remat + donation) through the hybrid trainer vs
             the same-mesh baseline config
             (``gpt_fast_tokens_per_sec``; needs >= 2 devices; CPU
             ratio ~1.0 documented — docs/PERF.md "Flagship tuning").
             The trainer-leg configs are the declarative
             ``BENCH_TRAIN_CONFIGS`` table, statically validated by
             ``scripts/check_bench_configs.py``.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# persistent compile cache: the bench programs are identical across runs,
# so a warm cache turns the cold-compile wall into seconds. Placed by
# JAX_COMPILATION_CACHE_DIR when set, else <checkout>/.jax_cache
# (apex_tpu/utils/compile_cache.py — the one place that decides).
from apex_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache)

enable_compile_cache()

A100_AMP_RN50_IMGS_PER_SEC = 2470.0  # per-chip baseline (see docstring)

# peak-flops table + cost_analysis extraction + MFU math live in
# observability.costs (shared with StepReporter's perf/mfu gauge) — one
# source of truth for peak-flops numbers.
from apex_tpu.observability.costs import (  # noqa: E402
    flops_budget, memory_budget as _memory_budget,
    peak_flops as _peak_flops)


def _mem_extra(compiled) -> dict:
    """``temp_bytes``/``peak_hbm_bytes`` extras for a bench line, from the
    compiled step's memory analysis — {} when the backend reports none, so
    emitted lines never carry fabricated zeros. Every ``bench_gpt_*``
    entry records these so the perf trajectory tracks memory next to
    step_ms."""
    budget = _memory_budget(compiled)
    if budget is None:
        return {}
    return {"temp_bytes": int(budget["temp_bytes"]),
            "peak_hbm_bytes": int(budget["peak_hbm_bytes"])}


def _attrib_extra(traced, step_ms) -> dict:
    """``modeled_step_ms``/``comm_exposed_ms``/``overlap_efficiency``
    extras for a bench line: the pyprof roofline lower bound of the
    traced step, the modeled communication the measured step failed to
    hide (0.0 on comm-free single-chip programs), and the fraction of
    modeled ICI bytes that rode under compute (absent on comm-free
    programs) — so bench rounds track *exposure*, not just step_ms (see
    docs/OBSERVABILITY.md "Step-time attribution"). {} when the model
    cannot price the program, so lines never carry fabricated numbers."""
    try:
        from apex_tpu.pyprof import attribute
        rep = attribute(traced, step_ms / 1e3)
        out = {"modeled_step_ms": round(rep.modeled_step_ms, 3),
               "step_time_ms": round(float(step_ms), 3)}
        if rep.comm_exposed_ms is not None:
            out["comm_exposed_ms"] = round(rep.comm_exposed_ms, 3)
        if rep.overlap_efficiency is not None:
            out["overlap_efficiency"] = round(rep.overlap_efficiency, 4)
        # the per-region breakdown rides ONLY into BENCH_HISTORY.jsonl
        # (popped from the printed line by _emit): perfwatch's
        # AttributionDiff names the region whose ms moved when a later
        # round regresses (docs/OBSERVABILITY.md "Performance
        # observatory")
        out["attribution"] = [
            {"region": r.name, "modeled_ms": round(r.modeled_ms, 4),
             **({} if r.measured_ms is None
                else {"measured_ms": round(r.measured_ms, 4)})}
            for r in rep.regions]
        return out
    except Exception:
        return {}


def _trace_and_compile(jitted, *args):
    """AOT ``(traced, compiled)`` of a jitted step: the traced stage keeps
    the jaxpr the pyprof attribution walks, ``.lower().compile()`` is the
    identical executable the timing loop runs."""
    traced = jitted.trace(*args)
    return traced, traced.lower().compile()


def _sync(out) -> None:
    """Drain the device queue — the shared fence lives in
    :func:`apex_tpu.utils.timers.device_fence`."""
    from apex_tpu.utils.timers import device_fence
    device_fence(out)


def _timeit(fn, args, iters, warmup, chunk=10):
    """Mean per-iteration wall times (seconds), measured in chunks of
    ``chunk`` iterations with one fetch-sync per chunk (minus the measured
    fetch round-trip). Args are threaded through so donated/carried state
    stays realistic. Per-chunk timing (not per-iteration) matters: a sync
    per step would add the host's dispatch-and-fetch round trip to every
    step — steps inside a chunk queue asynchronously and the chunk wall
    time is device-bound."""
    out = args
    for _ in range(warmup):
        out = fn(*out)
    _sync(out)
    rtt = min(_timed(lambda: _sync(out)) for _ in range(5))
    per_iter = []
    for _ in range(max(1, iters // chunk)):
        t0 = time.perf_counter()
        for _ in range(chunk):
            out = fn(*out)
        _sync(out)
        per_iter.append(max(time.perf_counter() - t0 - rtt, 1e-9) / chunk)
    return np.asarray(per_iter)


def _timed(f) -> float:
    t0 = time.perf_counter()
    f()
    return time.perf_counter() - t0


_RESULTS = []
_HISTORY = None


def _history():
    """The append target for the performance observatory
    (``BENCH_HISTORY.jsonl`` next to this script; ``APEX_BENCH_HISTORY``
    overrides the path, ``=off`` disables). Lazy and failure-proof —
    longitudinal bookkeeping must never break a bench run."""
    global _HISTORY
    if _HISTORY is None:
        try:
            from apex_tpu.observability.perfwatch import BenchHistory
            dest = os.environ.get(
                "APEX_BENCH_HISTORY",
                os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_HISTORY.jsonl"))
            _HISTORY = False if dest.lower() in ("", "0", "off", "none") \
                else BenchHistory(dest)
        except Exception:
            _HISTORY = False
    # explicit False check: an EMPTY BenchHistory is len()-falsy
    return None if _HISTORY is False else _HISTORY


def _emit(metric, value, unit, vs_baseline, **extra):
    # the per-region attribution block and the drift numerator are
    # history-only: printed lines (and BENCH_CONFIGS.json) keep their
    # pre-observatory shape, the cross-run differ is the only consumer
    attribution = extra.pop("attribution", None)
    step_time_ms = extra.pop("step_time_ms", None)
    line = {"metric": metric, "value": round(float(value), 2), "unit": unit,
            "vs_baseline": (None if vs_baseline is None
                            else round(float(vs_baseline), 4))}
    line.update(extra)
    _RESULTS.append(line)
    print(json.dumps(line), flush=True)
    hist = _history()
    if hist is not None:
        try:
            extras = dict(extra)
            if attribution is not None:
                extras["attribution"] = attribution
            if step_time_ms is not None:
                extras["step_time_ms"] = step_time_ms
            # raw_value carries full precision: the printed 2-decimal
            # value quantizes away sub-0.5% deltas (the class of bug
            # that forced gpt_decode_goodput into percent), and the
            # regression detector needs them
            hist.record(metric, value, unit, vs_baseline,
                        raw_value=float(value),
                        run=os.environ.get("BENCH_RUN"),
                        source="bench", extras=extras)
        except Exception:
            pass


def bench_headline(iters=50, warmup=5):
    from apex_tpu.amp.scaler import DynamicLossScale, all_finite
    from apex_tpu.models import ResNet50, ResNetConfig
    from apex_tpu.optimizers import FlatOptimizer, FusedSGD

    batch, img = 256, 224
    cfg = ResNetConfig(num_classes=1000, compute_dtype=jnp.bfloat16)
    model = ResNet50(cfg)
    params, bn_state = model.init(jax.random.PRNGKey(0))
    opt = FlatOptimizer(FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4))
    opt_state = opt.init(params)
    scaler = DynamicLossScale(init_scale=2.0 ** 12)
    ls = scaler.init()

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, img, img, 3), jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, 1000, batch))

    def loss_fn(params, bn_state, scale):
        logits, new_bn = model(params, bn_state, x, training=True)
        onehot = jax.nn.one_hot(labels, 1000)
        loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
        return loss * scale, (loss, new_bn)

    # params/bn/opt-state/scale are donated: the step updates them in place,
    # which avoids a full-parameter copy per iteration on HBM.
    @(lambda f: jax.jit(f, donate_argnums=(0, 1, 2, 3)))
    def step(params, bn_state, opt_state, ls):
        grads, (loss, new_bn) = jax.grad(loss_fn, has_aux=True)(
            params, bn_state, ls.loss_scale)
        finite = all_finite(grads)
        new_ls = scaler.update(ls, finite)
        # unscale fused into the optimizer update (the reference passes
        # 1/scale straight into the fused kernels the same way,
        # reference:apex/optimizers/fused_sgd.py:100-226) — one fewer full
        # pass over the gradients than a separate scaler.unscale
        params, opt_state = opt.step(grads, opt_state, params,
                                     grads_finite=finite,
                                     scale=1.0 / ls.loss_scale)
        return params, new_bn, opt_state, new_ls

    # model flops per step from the compiled executable (includes fwd+bwd+
    # optimizer); falls back to the analytic RN50 figure (2*4.1 GMACs fwd,
    # x3 for train) if the backend has no cost analysis. The compiled
    # executable is reused for the timing loop so the program compiles once.
    traced, compiled = _trace_and_compile(step, params, bn_state,
                                          opt_state, ls)
    flops_per_step = flops_budget(compiled)
    if flops_per_step is None:
        flops_per_step = 3 * 2 * 4.1e9 * batch

    times = _timeit(compiled, (params, bn_state, opt_state, ls),
                    iters, warmup)
    step_ms = float(np.mean(times) * 1e3)
    imgs_per_sec = batch / float(np.mean(times))
    mfu = flops_per_step / float(np.mean(times)) / _peak_flops()
    _emit("resnet50_train_imgs_per_sec_per_chip", imgs_per_sec, "imgs/sec",
          imgs_per_sec / A100_AMP_RN50_IMGS_PER_SEC,
          step_ms=round(step_ms, 3),
          std_ms=round(float(np.std(times) * 1e3), 3),
          mfu=round(mfu, 4), iters=iters,
          **_attrib_extra(traced, step_ms))


def _device_loop_ms(step_fn, init_carry, k=50, reps=5):
    """Time ``step_fn`` (carry -> carry) by scanning it ``k`` times inside
    ONE jitted call — per-call host dispatch can exceed a sub-ms kernel
    by 10x, so micro-kernels must loop on device.
    Returns (mean_ms, std_ms) over ``reps`` calls."""
    @jax.jit
    def many(carry):
        return jax.lax.scan(lambda c, _: (step_fn(c), None), carry,
                            None, length=k)[0]

    out = many(init_carry)
    _sync(out)
    rtt = min(_timed(lambda: _sync(out)) for _ in range(3))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = many(out)
        _sync(out)
        times.append(max(time.perf_counter() - t0 - rtt, 1e-9) / k)
    return (float(np.mean(times) * 1e3), float(np.std(times) * 1e3))


def bench_layernorm():
    """BASELINE config 2: LN fwd+bwd. Reports the library's AUTO-selected
    path (measured policy: XLA at every hidden size — see
    ``normalization/_pallas.py:prefer_pallas``) against the forced-Pallas
    kernel, at a transformer-typical shape and at the large-hidden regime
    the reference's ``fast_layer_norm`` targets."""
    from apex_tpu.normalization import fused_layer_norm_affine

    def measure(rows, hidden, use_pallas):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(rows, hidden), jnp.bfloat16)
        w = jnp.asarray(rng.randn(hidden), jnp.float32)
        b = jnp.asarray(rng.randn(hidden), jnp.float32)

        def loss(x, w, b):
            y = fused_layer_norm_affine(x, w, b, (hidden,),
                                        use_pallas=use_pallas)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        def step(carry):
            x, w, b = carry
            dx, dw, db = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
            # thread all three grads so nothing is dead-code-eliminated
            return 0.1 * dx, w + 1e-30 * dw, b + 1e-30 * db

        return _device_loop_ms(step, (x, w, b), k=100)

    for rows, hidden in [(8192, 4096), (1024, 32768)]:
        auto_ms, auto_std = measure(rows, hidden, None)
        pallas_ms, _ = measure(rows, hidden, True)
        # metric renamed from layernorm_fwd_bwd_ms (r5): the old name's
        # vs_baseline flipped meaning mid-history (xla_ms/pallas_ms on the
        # Pallas time -> pallas_ms/auto_ms on the auto time); the new name
        # pins the auto-path semantics so cross-round consumers can't
        # silently compare inverted ratios (ADVICE.md r5, BASELINE.md)
        _emit("layernorm_auto_fwd_bwd_ms", auto_ms, "ms",
              pallas_ms / auto_ms,
              rows=rows, hidden=hidden, selected_path="xla",
              pallas_ms=round(pallas_ms, 3), std_ms=round(auto_std, 3))


def bench_optimizer():
    """BASELINE config 3: FusedAdam step time over an RN50-sized param tree —
    per-leaf tree_map vs the persistent-flat FlatOptimizer tier (state stays
    flat across steps; grads arrive flat, as the grad-w.r.t.-flat training
    pattern produces). A second point stresses leaf-count pathology (1024
    tiny leaves), the regime ``multi_tensor_apply`` exists for."""
    from apex_tpu.models import ResNet50, ResNetConfig
    from apex_tpu.optimizers import FlatOptimizer, FusedAdam

    def run_per_leaf(params, grads, k=20):
        opt = FusedAdam(lr=1e-3)
        state = opt.init(params)

        def step(carry):
            p, s = carry
            return opt.step(grads, s, p)

        return _device_loop_ms(step, (params, state), k=k)

    def run_flat(params, k=20):
        opt = FlatOptimizer(FusedAdam(lr=1e-3))
        fstate = opt.init_flat(params)
        flat_grads = jnp.full_like(fstate.flat_params, 1e-4)

        def step(fstate):
            return opt.flat_step(flat_grads, fstate)

        return _device_loop_ms(step, fstate, k=k)

    model = ResNet50(ResNetConfig(num_classes=1000))
    params, _ = model.init(jax.random.PRNGKey(0))
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full(jnp.shape(p), 1e-4, jnp.float32), params)
    leaf_ms, _ = run_per_leaf(params, grads)
    flat_ms, flat_std = run_flat(params)
    n_leaves = len(jax.tree_util.tree_leaves(params))

    # leaf-count pathology point, the regime multi_tensor_apply exists for.
    # 512 leaves (not 1024): a >1000-op per-leaf program once hit a
    # transient remote-compile INTERNAL error at the 590s budget (r4
    # verdict); guarded so a compile blowup cannot sink the whole run.
    many = {f"p{i}": jnp.full((1024,), 0.1, jnp.float32)
            for i in range(512)}
    many_grads = jax.tree_util.tree_map(
        lambda p: jnp.full_like(p, 1e-4), many)
    try:
        many_leaf_ms, _ = run_per_leaf(many, many_grads)
        many_flat_ms, _ = run_flat(many)
        many_leaf_ms = round(many_leaf_ms, 3)
        many_flat_ms = round(many_flat_ms, 3)
    except Exception:
        many_leaf_ms = many_flat_ms = None

    _emit("fused_adam_step_ms_flat", flat_ms, "ms", leaf_ms / flat_ms,
          per_leaf_ms=round(leaf_ms, 3), n_leaves=n_leaves,
          std_ms=round(flat_std, 3),
          leaves512_flat_ms=many_flat_ms,
          leaves512_per_leaf_ms=many_leaf_ms)


def _gpt_train_step(batch=8, seq=1024, hidden=768, layers=12, heads=12,
                    vocab=32768, remat_policy=None, **cfg_overrides):
    """The canonical config-5 GPT-small train step (flash attention,
    FusedAdam, dynamic loss scaling, donated buffers), AOT-compiled.
    Shared by :func:`bench_gpt` (the baseline row), every
    :func:`bench_gpt_remat` leg, and ``scripts/attribute_step.py`` (which
    passes ``compute_dtype``/``use_flash``/``layer_scan_unroll`` through
    ``cfg_overrides`` to build its XLA-countable validation twin of the
    SAME program), so neither the remat sweep nor the attribution
    instrument can drift from the baseline step. ``cfg_overrides`` are
    extra :class:`GPTConfig` fields laid over the bench defaults.
    Returns ``(cfg, args, wrapped, compiled, traced)``: ``wrapped(*args)``
    runs one step and threads the donated buffers back as the next
    call's args (the `_timeit` convention); ``traced`` is the
    pre-lowering stage the pyprof attribution
    (``modeled_step_ms``/``comm_exposed_ms`` columns) walks."""
    from apex_tpu.amp.scaler import DynamicLossScale, all_finite
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.optimizers import FusedAdam

    cfg_kw = dict(vocab_size=vocab, hidden_size=hidden,
                  num_layers=layers, num_attention_heads=heads,
                  max_position_embeddings=seq,
                  compute_dtype=jnp.bfloat16, remat_policy=remat_policy)
    cfg_kw.update(cfg_overrides)
    cfg = GPTConfig(**cfg_kw)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    opt = FusedAdam(lr=1e-4)
    opt_state = opt.init(params)
    scaler = DynamicLossScale(init_scale=2.0 ** 12)
    ls = scaler.init()
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, vocab, (batch, seq)))

    @(lambda f: jax.jit(f, donate_argnums=(0, 1, 2)))
    def step(params, opt_state, ls, tokens):
        def loss_fn(p):
            return model.loss(p, tokens, tokens) * ls.loss_scale
        grads = jax.grad(loss_fn)(params)
        grads = scaler.unscale(ls, grads)
        finite = all_finite(grads)
        new_ls = scaler.update(ls, finite)
        params, opt_state = opt.step(grads, opt_state, params,
                                     grads_finite=finite)
        return params, opt_state, new_ls

    traced, compiled = _trace_and_compile(step, params, opt_state, ls,
                                          tokens)

    def wrapped(params, opt_state, ls, tokens):
        params, opt_state, ls = compiled(params, opt_state, ls, tokens)
        return params, opt_state, ls, tokens

    return cfg, (params, opt_state, ls, tokens), wrapped, compiled, traced


def bench_gpt(iters=20, warmup=3):
    """BASELINE config 5: GPT-small train step on one chip — times the
    Mosaic-compiled flash-attention kernels end to end (fwd+bwd), FusedAdam,
    dynamic loss scaling."""
    batch, seq = 8, 1024
    cfg, args, wrapped, compiled, traced = _gpt_train_step(batch=batch,
                                                           seq=seq)
    params = args[0]
    times = _timeit(wrapped, args, iters, warmup)
    tok_per_sec = batch * seq / float(np.mean(times))

    # anchor: 40% MFU — the published llm.c/nanoGPT-class utilization for
    # GPT-2-124M-scale A100 training — over THIS chip's peak. Model flops
    # use the standard analytic count (llm.c / PaLM-appendix convention:
    # 6N per token for the parameter matmuls fwd+bwd, plus 12*L*d_model*seq
    # for attention) — XLA's cost_analysis cannot be used here because the
    # Mosaic flash-attention custom calls report zero flops, deflating MFU
    # ~4x. vs_baseline > 1 means the step beats the 40%-MFU standard; the
    # reference publishes no GPT numbers (BASELINE.md) so a utilization
    # anchor is the defensible comparison.
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    flops_per_tok = (6.0 * n_params
                     + 12.0 * cfg.num_layers * cfg.hidden_size * seq)
    vs_anchor = tok_per_sec / (0.40 * _peak_flops() / flops_per_tok)
    mfu = tok_per_sec * flops_per_tok / _peak_flops()
    step_ms = float(np.mean(times) * 1e3)
    _emit("gpt_small_train_tokens_per_sec", tok_per_sec, "tokens/sec",
          vs_anchor, anchor="40pct_mfu_this_chip",
          mfu=round(float(mfu), 4),
          step_ms=round(step_ms, 3),
          std_ms=round(float(np.std(times) * 1e3), 3),
          batch=batch, seq=seq, **_mem_extra(compiled),
          **_attrib_extra(traced, step_ms))


def bench_gpt_remat(iters=10, warmup=2, batch=8, seq=1024, hidden=768,
                    layers=12, heads=12, vocab=32768):
    """Activation-remat memory/compute frontier A/B: the BASELINE config-5
    GPT-small train step swept over the four
    :class:`~apex_tpu.remat.RematPolicy` modes in one session — same
    shapes, same data, fresh params per leg, so the deltas isolate the
    policy. Per policy two lines ride BENCH_*.json:

    - ``gpt_remat_<policy>_step_ms`` (vs_baseline = none_ms/policy_ms,
      < 1 means the policy pays recompute FLOPs);
    - ``gpt_remat_<policy>_temp_bytes`` (vs_baseline =
      policy_temp/none_temp, the fraction of the activation working set
      kept resident).

    Every leg is built by :func:`_gpt_train_step` — the same constructor
    as the ``gpt_small_train_tokens_per_sec`` baseline row — so the sweep
    cannot drift from the program the baseline measures.

    Expected/asserted-in-tests ordering: temp_bytes none > selective >
    full — selective keeps only the registry-tagged GEMM/flash outputs,
    full keeps only the scan carry. ``offload`` compiles everywhere but
    its byte movement only means something where pinned_host is a real
    second memory space (TPU); read its step_ms there
    (docs/PERF.md "Remat & HBM")."""
    def measure(policy):
        _cfg, args, wrapped, compiled, traced = _gpt_train_step(
            batch=batch, seq=seq, hidden=hidden, layers=layers,
            heads=heads, vocab=vocab, remat_policy=policy)
        mem = _mem_extra(compiled)
        times = _timeit(wrapped, args, iters, warmup)
        ms = float(np.mean(times) * 1e3)
        mem.update(_attrib_extra(traced, ms))
        return ms, float(np.std(times) * 1e3), mem

    results = {}
    for policy in ("none", "selective", "full", "offload"):
        try:
            results[policy] = measure(policy)
        except Exception as e:  # one leg must not sink the sweep
            results[policy] = e
    base = results.get("none")
    base_ms = base[0] if isinstance(base, tuple) else None
    base_temp = (base[2].get("temp_bytes")
                 if isinstance(base, tuple) else None)
    for policy, r in results.items():
        if isinstance(r, Exception):
            _emit(f"gpt_remat_{policy}_step_ms", -1.0, "error", None,
                  error=str(r))
            continue
        ms, std, mem = r
        _emit(f"gpt_remat_{policy}_step_ms", ms, "ms",
              None if (base_ms is None or policy == "none")
              else base_ms / ms,
              std_ms=round(std, 3), batch=batch, seq=seq, iters=iters,
              **mem)
        if "temp_bytes" in mem:
            _emit(f"gpt_remat_{policy}_temp_bytes", mem["temp_bytes"],
                  "bytes",
                  None if (not base_temp or policy == "none")
                  else mem["temp_bytes"] / base_temp,
                  peak_hbm_bytes=mem.get("peak_hbm_bytes"))


# Declarative trainer-driven bench configs (fmengine-style: the config
# surface a tuned compound run needs, as data). Keys are REAL
# TrainConfig/ModelConfig/OptimizerConfig field names — statically
# validated by scripts/check_bench_configs.py (wired into tier-1), so a
# renamed flag breaks the check instead of silently dropping a leg back
# to defaults. "gpt_base" is the headline config-5 shape through the
# hybrid trainer; "gpt_fast" is the compound overlap preset laid over it
# — the same knobs TrainConfig.fastpath() applies (asserted equal in
# tests/test_fastpath.py, so this record cannot drift from the preset;
# fastpath() additionally turns on sequence_parallel + tp_comm_overlap
# when the mesh/jax can carry them).
BENCH_TRAIN_CONFIGS = {
    "gpt_base": {
        "model": {"name": "gpt", "vocab_size": 32768, "hidden_size": 768,
                  "num_layers": 12, "num_attention_heads": 12,
                  "max_position_embeddings": 1024},
        "optimizer": {"name": "adam", "lr": 1e-4, "weight_decay": 0.01},
        "opt_level": "O2",
        "half_dtype": "bfloat16",
    },
    "gpt_fast": {
        "model": {"remat_policy": "selective"},
        "optimizer": {"zero": 1},
        "ddp_bucket_bytes": "auto",
    },
}


def _train_config_from_spec(*specs, parallel=None, batch=None):
    """Merge declarative spec dicts (later wins, nested sections update)
    into a TrainConfig; unknown keys fail in the dataclass constructors
    (and statically in scripts/check_bench_configs.py)."""
    from apex_tpu.config import TrainConfig

    merged = {}
    for spec in specs:
        for k, v in spec.items():
            if isinstance(v, dict):
                merged.setdefault(k, {}).update(v)
            else:
                merged[k] = v
    if parallel is not None:
        merged["parallel"] = dict(parallel)
    if batch is not None:
        merged["batch"] = dict(batch)
    return TrainConfig.from_dict(merged)


def bench_gpt_fast(iters=10, warmup=2, mb=8, seq=1024, max_devices=None):
    """Compound fastpath A/B: the headline GPT-small shape through the
    hybrid trainer on the full device set, baseline config vs
    ``TrainConfig.fastpath()`` — tp_comm_overlap (mesh/jax permitting) +
    bucketed DP + ZeRO-1 with backward-interleaved per-bucket RS→math→AG
    + selective remat + donated state, the first time every overlap
    feature is compounded on the flagship bench. Same session, same
    mesh, same data; ``vs_baseline`` is fast/base tokens-per-sec (> 1
    means the compound config pays). ``bucket_bytes`` in the line is the
    roofline-resolved ``"auto"`` grid. On a CPU host mesh there is no
    ICI latency to hide, so ~1.0 is the expected and documented reading
    (docs/PERF.md "Flagship tuning") — the win must be read off a
    multi-chip run, where ``overlap_efficiency``/``comm_exposed_ms`` on
    this line say how much of the modeled traffic actually hid. Skipped
    below 2 devices (the compound config is comm machinery; single-chip
    deltas are the remat bench's job)."""
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    if jax.device_count() < 2:
        _emit("gpt_fast_tokens_per_sec", -1.0, "skipped", None,
              error=f"needs >= 2 devices, have {jax.device_count()}")
        return

    # tp=2 only where a data axis remains beside it; otherwise all devices go to dp. ``max_devices``
    # caps the mesh (the tier-1 smoke test runs this leg on 2 of the 8
    # virtual devices — compile cost scales with mesh width on CPU)
    n_dev = jax.device_count()
    if max_devices is not None:
        n_dev = min(n_dev, int(max_devices))
    tp = 2 if (n_dev % 2 == 0 and n_dev >= 4) else 1
    dp, M = n_dev // tp, 1
    parallel = {"tensor_model_parallel_size": tp,
                "pipeline_model_parallel_size": 1}
    batch = {"global_batch_size": M * mb * dp, "micro_batch_size": mb}
    base_cfg = _train_config_from_spec(BENCH_TRAIN_CONFIGS["gpt_base"],
                                       parallel=parallel, batch=batch)
    fast_cfg = base_cfg.fastpath()
    vocab = base_cfg.model.vocab_size
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, vocab, (M, dp * mb, seq)))
    targets = jnp.asarray(rng.randint(0, vocab, (M, dp * mb, seq)))

    def measure(cfg):
        mesh = cfg.initialize_mesh(devices=jax.devices()[: tp * dp])
        try:
            tr = GPTHybridTrainer(cfg, mesh)
            state = tr.init_state(jax.random.PRNGKey(0))
            jitted = jax.jit(tr.train_step, donate_argnums=(0, 1, 2))
            traced, compiled = _trace_and_compile(jitted, *state, tokens,
                                                  targets)

            def wrapped(s0, s1, s2, ls, tokens, targets):
                _loss, s0, s1, s2, ls = compiled(s0, s1, s2, ls, tokens,
                                                 targets)
                return s0, s1, s2, ls, tokens, targets

            times = _timeit(wrapped, (*state, tokens, targets), iters,
                            warmup)
            tps = M * dp * mb * seq / float(np.mean(times))
            return tps, times, _mem_extra(compiled), traced, tr
        finally:
            parallel_state.destroy_model_parallel()

    base_tps, _, _, _, _ = measure(base_cfg)
    fast_tps, times, mem, traced, tr = measure(fast_cfg)
    step_ms = float(np.mean(times) * 1e3)
    _emit("gpt_fast_tokens_per_sec", fast_tps, "tokens/sec",
          fast_tps / base_tps, base_tps=round(base_tps, 2),
          step_ms=round(step_ms, 3),
          std_ms=round(float(np.std(times) * 1e3), 3),
          tp=tp, dp=dp, batch=mb, seq=seq,
          # the resolved compound config, real field names only —
          # scripts/check_bench_configs.py validates these keys against
          # the dataclasses, so a renamed flag cannot ride along silently
          config={
              "model": {
                  "remat_policy": fast_cfg.model.remat_policy,
                  "sequence_parallel": fast_cfg.model.sequence_parallel,
                  "tp_comm_overlap": fast_cfg.model.tp_comm_overlap},
              "optimizer": {"zero": 1},
              "ddp_bucket_bytes": tr.bucket_bytes,
          },
          **mem, **_attrib_extra(traced, step_ms))


def bench_gpt_sp_overlap(iters=10, warmup=2, batch=8, seq=1024,
                         hidden=768, layers=12, heads=12, vocab=32768):
    """Dependent-collective overlap A/B: GPT-small fwd+bwd tokens/sec at
    TP=2 with Megatron sequence parallelism, ring-decomposed collective
    matmuls (``tensor_parallel/collective_matmul.py``) vs the fused
    all_gather/psum_scatter baseline — same session, same mesh, same
    params, so the ratio isolates the exposed-ICI-latency win.
    ``vs_baseline`` is overlap/fused (>1 means the decomposition pays).
    Skipped (emitted with an error note) below 2 devices."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.transformer import parallel_state
    from jax import shard_map

    if jax.device_count() < 2:
        _emit("gpt_sp_overlap_tokens_per_sec", -1.0, "skipped", None,
              error=f"needs >= 2 devices, have {jax.device_count()}")
        return

    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2, devices=jax.devices()[:2])
    try:
        kw = dict(vocab_size=vocab, hidden_size=hidden, num_layers=layers,
                  num_attention_heads=heads, max_position_embeddings=seq,
                  compute_dtype=jnp.bfloat16, tensor_model_parallel_size=2,
                  sequence_parallel=True)
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, vocab, (batch, seq)))
        base = GPTModel(GPTConfig(**kw))
        params = base.init(jax.random.PRNGKey(0))
        specs = base.param_specs(params)

        def measure(overlap):
            model = GPTModel(GPTConfig(**kw, tp_comm_overlap=overlap))

            def step_inner(params, tokens):
                loss, grads = jax.value_and_grad(
                    lambda p: model.loss(p, tokens, tokens))(params)
                # thread a trivial update so bwd isn't dead-code-eliminated
                new_p = jax.tree_util.tree_map(
                    lambda p, g: p - (1e-12 * g).astype(p.dtype),
                    params, grads)
                return new_p, jax.lax.pmean(
                    jax.lax.pmean(loss, "tensor"), "data")

            smapped = shard_map(step_inner, mesh=mesh,
                                in_specs=(specs, P()),
                                out_specs=(specs, P()))

            @(lambda f: jax.jit(f, donate_argnums=(0,)))
            def step(params, tokens):
                new_p, loss = smapped(params, tokens)
                return new_p, loss, tokens

            # fresh param buffers per variant: the donated originals are
            # consumed by the first call. AOT-compiled so the memory plan
            # (temp_bytes) is recorded alongside the timing.
            p0 = jax.tree_util.tree_map(jnp.copy, params)
            traced, compiled = _trace_and_compile(step, p0, tokens)

            def wrapped(params, loss, tokens):
                return compiled(params, tokens)

            times = _timeit(wrapped, (p0, jnp.float32(0.0), tokens),
                            iters, warmup)
            return (batch * seq / float(np.mean(times)), times,
                    _mem_extra(compiled), traced)

        fused_tps, _, _, _ = measure(False)
        overlap_tps, times, mem, traced = measure(True)
        step_ms = float(np.mean(times) * 1e3)
        # the attribution here prices the ring ppermute chains hop by hop
        # — comm_exposed_ms is the number the overlap machinery exists to
        # drive to zero (CPU hosts have no ICI; read it on a TPU run)
        _emit("gpt_sp_overlap_tokens_per_sec", overlap_tps, "tokens/sec",
              overlap_tps / fused_tps,
              fused_tps=round(fused_tps, 2), tp=2, batch=batch, seq=seq,
              step_ms=round(step_ms, 3),
              std_ms=round(float(np.std(times) * 1e3), 3), **mem,
              **_attrib_extra(traced, step_ms))
    finally:
        parallel_state.destroy_model_parallel()


def bench_dp_accumulate_overlap(iters=10, warmup=2, K=4, layers=8,
                                hidden=512, batch_per_rank=8):
    """Bucketed-DP overlap A/B: a gradient-accumulation window (K
    microbatches, local sum, one end-of-window sync) + FusedAdam step on a
    pure-DP mesh, monolithic sync (one psum per grad leaf at the window
    end) vs the bucketed engine
    (``parallel/distributed.py::allreduce_grads(bucket_bytes=...)``) —
    same session, same mesh, same params, so the ratio isolates what
    XLA's latency-hiding scheduler buys from B independent bucket
    collectives it can overlap with the finite-check/scale epilogue and
    each other. ``vs_baseline`` is mono_ms/bucket_ms (>1 means bucketing
    pays). On a CPU host mesh there is no ICI latency to hide, so ~1.0 is
    the expected and documented reading (docs/PERF.md "DP overlap +
    ZeRO") — the win must be read off a multi-chip run. Skipped below 2
    devices."""
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.parallel import DistributedDataParallel
    from apex_tpu.training import accumulate_gradients
    from jax import shard_map

    if jax.device_count() < 2:
        _emit("dp_window_overlap_step_ms", -1.0, "skipped", None,
              error=f"needs >= 2 devices, have {jax.device_count()}")
        return

    dp = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    rng = np.random.RandomState(0)
    widths = [hidden] * (layers + 1)
    params = {f"w{i}": jnp.asarray(
        rng.randn(widths[i], widths[i + 1]) * (widths[i] ** -0.5),
        jnp.float32) for i in range(layers)}
    xs = jnp.asarray(rng.randn(K, dp * batch_per_rank, hidden), jnp.float32)
    ys = jnp.asarray(rng.randn(K, dp * batch_per_rank, hidden), jnp.float32)

    def loss_fn(p, mb):
        x, y = mb
        h = x
        for i in range(layers):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    opt = FusedAdam(lr=1e-3)

    def measure(bucket_bytes):
        ddp = DistributedDataParallel("data", delay_allreduce=True,
                                      bucket_bytes=bucket_bytes)

        def window(p, s, xs, ys):
            def inner(p, s, xs, ys):
                loss, grads = accumulate_gradients(ddp, loss_fn, p,
                                                   (xs, ys))
                new_p, new_s = opt.step(grads, s, p)
                return jax.lax.pmean(loss, "data"), new_p, new_s
            pspec = jax.tree_util.tree_map(lambda _: P(), p)
            sspec = jax.tree_util.tree_map(lambda _: P(), s)
            return shard_map(
                inner, mesh=mesh,
                in_specs=(pspec, sspec, P(None, "data"), P(None, "data")),
                out_specs=(P(), pspec, sspec))(p, s, xs, ys)

        @(lambda f: jax.jit(f, donate_argnums=(0, 1)))
        def step(p, s, xs, ys):
            _, new_p, new_s = window(p, s, xs, ys)
            return new_p, new_s, xs, ys

        p0 = jax.tree_util.tree_map(jnp.copy, params)
        s0 = opt.init(p0)
        times = _timeit(step, (p0, s0, xs, ys), iters, warmup)
        return float(np.mean(times) * 1e3), times

    mono_ms, _ = measure(None)
    from apex_tpu.parallel.distributed import DEFAULT_BUCKET_BYTES
    # params are ~layers*hidden^2*4 bytes; pick a bucket ~1/8 of that so
    # several buckets are in flight even at bench scale, capped at the
    # library default
    bb = min(DEFAULT_BUCKET_BYTES,
             max(1 << 16, (layers * hidden * hidden * 4) // 8))
    bucket_ms, times = measure(bb)
    _emit("dp_window_overlap_step_ms", bucket_ms, "ms",
          mono_ms / bucket_ms, mono_ms=round(mono_ms, 3),
          bucket_bytes=bb, dp=dp, num_micro=K,
          std_ms=round(float(np.std(times) * 1e3), 3))


# the stated serving SLO the decode goodput is scored under:
# (metric, quantile, threshold_ms). Production-shaped thresholds — on the
# CPU test host the absolute latencies are structural, so read goodput
# off a TPU round (BASELINE.md round 13).
DECODE_SLO = (("ttft_ms", 95.0, 2000.0), ("tpot_ms", 99.0, 500.0))

# Declarative decode leg config: keys are REAL
# ``ServingEngine.__init__`` keyword parameters — statically
# validated by scripts/check_bench_configs.py (rule ast-bench-configs),
# so a renamed engine knob breaks the check instead of TypeError-ing
# only at bench runtime. num_blocks = max_seqs * (max_len/block_size)
# + 1 (the reserved null block): every slot's whole max_len, the
# engine's own default, so the throughput reads the bounded-grid kernel,
# not admission pressure. mean_context prices the kernel's CostEstimate
# at the fleet's expected live context (docs/SERVING.md "Paged
# serving").
BENCH_DECODE_CONFIGS = {
    "gpt_decode_paged": {
        "max_seqs": 8, "max_len": 1024, "prefill_len": 128,
        "block_size": 128, "num_blocks": 65, "mean_context": 160.0,
    },
    # the speculative A/B leg: the pool left to the engine's default,
    # small batch where decode is deepest into the memory-bound regime and
    # speculation's k-tokens-per-step amortization reads clearest;
    # speculate_k >= 1 is enforced statically (k=0 would silently bench
    # the non-speculative path against itself)
    "gpt_decode_spec": {
        "max_seqs": 4, "max_len": 1024, "prefill_len": 128,
        "speculate_k": 4,
    },
}


def bench_gpt_decode_paged(iters=20, warmup=3, prefix_reps=5, hidden=768,
                           layers=12, heads=12, vocab=32768):
    """Paged serving legs (docs/SERVING.md "Paged serving"): the same
    GPT-small shape through the AOT ``ServingEngine`` — block-pool
    KV cache, bounded-grid decode kernel, copy-on-write prefix sharing.
    The engine config is the declarative
    ``BENCH_DECODE_CONFIGS["gpt_decode_paged"]`` entry, statically
    validated by scripts/check_bench_configs.py.

    - ``gpt_decode_tok_per_sec_paged``: every slot of the grid
      active. ``vs_baseline`` is measured/roofline over the
      live-stripe HBM bound at the actual mean context.
    - ``gpt_decode_ttft_prefix_ms``: prefill latency for a prompt whose
      prefix is already registered in the pool (maps the shared blocks,
      decodes only the un-shared tail) vs the same-length cold path.
      ``vs_baseline`` is cold/warm (> 1 means prefix sharing pays);
      ``ttft_cold_ms`` rides the line.

    CPU numbers are structural (interpret-mode kernels); read real
    latencies off a TPU run."""
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.observability.costs import device_spec
    from apex_tpu.serving import (BlockAllocator, PagedKVCache,
                                  ServingEngine)

    spec = dict(BENCH_DECODE_CONFIGS["gpt_decode_paged"])
    slots, max_len = spec["max_seqs"], spec["max_len"]
    prefill_len, block_size = spec["prefill_len"], spec["block_size"]
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_attention_heads=heads,
                    max_position_embeddings=max_len,
                    compute_dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    param_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree_util.tree_leaves(params))
    rs = np.random.RandomState(0)
    eng = ServingEngine(model, params, **spec)

    # --- TTFT leg first (the throughput _timeit consumes the donated
    # cache outside the engine's bookkeeping) ---
    shared = rs.randint(1, vocab, size=prefill_len).tolist()
    cold_ms = []
    for _ in range(prefix_reps):
        # distinct prompts so every rep takes the cold path
        t0 = time.perf_counter()
        eng.prefill(rs.randint(1, vocab, size=prefill_len).tolist(),
                    slot=0)
        cold_ms.append((time.perf_counter() - t0) * 1e3)
        eng.release_slot(0)
    eng.prefill(shared, slot=0)  # registers the shared prefix
    warm_ms = []
    for _ in range(prefix_reps):
        t0 = time.perf_counter()
        eng.prefill(shared, slot=1)
        warm_ms.append((time.perf_counter() - t0) * 1e3)
        assert not eng.last_admit.prefill, "prefix hit expected"
        eng.release_slot(1)
    eng.release_slot(0)
    cold = float(np.median(cold_ms))
    warm = float(np.median(warm_ms))

    # --- throughput leg: fresh pool, distinct prompts (no sharing —
    # the COW/refcount cost is the allocator tests' job), one host-path
    # step so every slot owns a live decode block, then the frozen
    # compiled step threaded the _timeit way ---
    eng.cache = PagedKVCache.create(layers, spec["num_blocks"], heads,
                                    block_size, cfg.head_dim,
                                    dtype=jnp.bfloat16)
    eng.allocator = BlockAllocator(spec["num_blocks"], block_size,
                                   eng.allocator.blocks_per_slot, slots)
    for s in range(slots):
        eng.prefill(rs.randint(1, vocab, size=prefill_len).tolist(),
                    slot=s)
    eng.decode(np.zeros(slots, np.int32), np.zeros(slots, np.float32))
    alloc = eng.allocator
    bids, offs = alloc.append_targets(np.ones(slots, bool))
    tables = jnp.asarray(alloc.tables)
    lengths = jnp.asarray(alloc.lengths)
    temps = jnp.zeros((slots,), jnp.float32)
    zs = jnp.zeros((slots,), jnp.int32)
    bids, offs = jnp.asarray(bids), jnp.asarray(offs)
    key = eng._next_key()

    def dwrap(cache, toks):
        cache, toks = eng.decode_compiled(params, cache, tables, lengths,
                                          toks, temps, bids, offs, zs,
                                          zs, key)
        return cache, toks

    times = _timeit(dwrap, (eng.cache, zs), iters, warmup)
    step_ms = float(np.mean(times) * 1e3)
    tok_per_sec = slots / float(np.mean(times))

    # the live-stripe roofline at the ACTUAL mean context — the step's
    # HBM target, not max_len's
    mean_len = float(np.mean(np.asarray(alloc.lengths)))
    stripe = (2 * layers * heads * mean_len * cfg.head_dim
              * jnp.dtype(jnp.bfloat16).itemsize)
    dspec = device_spec()
    roofline = slots / ((param_bytes + slots * stripe)
                        / (dspec.hbm_gbps * 1e9))

    extras = dict(_mem_extra(eng.decode_compiled))
    extras.update(_attrib_extra(eng.decode_traced, step_ms))
    _emit("gpt_decode_tok_per_sec_paged", tok_per_sec, "tokens/sec",
          tok_per_sec / roofline, anchor="hbm_roofline_this_chip",
          roofline_tok_per_sec=round(roofline, 2),
          step_ms=round(step_ms, 3),
          std_ms=round(float(np.std(times) * 1e3), 3),
          slots=slots, max_len=max_len, prefill_len=prefill_len,
          block_size=block_size, num_blocks=spec["num_blocks"],
          mean_context=spec["mean_context"], iters=iters, **extras)
    _emit("gpt_decode_ttft_prefix_ms", warm,
          "ms", None if warm <= 0 else cold / warm,
          ttft_cold_ms=round(cold, 3), prefill_len=prefill_len,
          shared_tokens=prefill_len - 1, reps=prefix_reps)


def bench_gpt_decode_spec(new_tokens=48, requests=8, hidden=768,
                          layers=12, heads=12, vocab=32768):
    """Speculative-decoding A/B (docs/SERVING.md "Speculative
    decoding"): the SAME GPT-small weights and request set through a
    non-speculative engine and a ``speculate_k`` one
    (``BENCH_DECODE_CONFIGS["gpt_decode_spec"]``), both driven by the
    full scheduler loop so the number includes the host drafting cost.

    - ``gpt_decode_tok_per_sec_spec``: end-to-end generated tokens per
      second under speculation; ``vs_baseline`` is the ratio against
      the same-session non-speculative run (> 1 means speculation
      pays), with the non-spec rate, acceptance rate and verify-step
      count riding the line.

    Workload: repetitive text — greedy decoding of a random-weight
    GPT settles into short repetition loops, exactly the regime
    prompt-lookup drafting serves (real repetitive workloads: code,
    templated prose, retrieval contexts). The win is k tokens per
    memory-bound step at ~1 step's HBM traffic; CPU numbers compress
    it (the XLA-fallback verify pays k× compute that a TPU hides under
    the HBM stream — BASELINE.md carries the sandbox ratio), so read
    the real delta off a TPU run."""
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.observability.registry import MetricsRegistry
    from apex_tpu.serving import Request, ServingEngine, SlotScheduler

    spec = dict(BENCH_DECODE_CONFIGS["gpt_decode_spec"])
    k = spec["speculate_k"]
    slots, max_len = spec["max_seqs"], spec["max_len"]
    prefill_len = spec["prefill_len"]
    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_layers=layers, num_attention_heads=heads,
                    max_position_embeddings=max_len,
                    compute_dtype=jnp.bfloat16)
    model = GPTModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pattern = np.random.RandomState(0).randint(
        1, vocab, size=8).tolist()

    def leg(speculate):
        eng = ServingEngine(model, params,
                            **{**spec, "speculate_k": speculate})
        reg = MetricsRegistry()
        sched = SlotScheduler(eng, registry=reg, speculate_k=speculate)
        # warm run: first-dispatch host paths + any lazy sampling
        # compiles, outside the timed window
        sched.run([Request(prompt=pattern, max_new_tokens=2)])
        reqs = [Request(prompt=(pattern * 32)[i: i + prefill_len],
                        max_new_tokens=new_tokens)
                for i in range(requests)]
        t0 = time.perf_counter()
        done = sched.run(reqs)
        dt = time.perf_counter() - t0
        gen = sum(len(c.tokens) for c in done.values())
        return gen / dt, dict(reg.snapshot())

    base_tps, _ = leg(0)
    spec_tps, snap = leg(k)
    _emit("gpt_decode_tok_per_sec_spec", spec_tps, "tokens/sec",
          None if base_tps <= 0 else spec_tps / base_tps,
          anchor="same_session_nonspec_ab",
          nonspec_tok_per_sec=round(base_tps, 2),
          accept_rate=round(snap.get("serve/spec_accept_rate", 0.0), 4),
          spec_steps=int(snap.get("serve/spec_steps", 0)),
          speculate_k=k, slots=slots, max_len=max_len,
          prefill_len=prefill_len, new_tokens=new_tokens,
          requests=requests)


def bench_flash_long(seq=4096, b=8, h=12, d=64):
    """Long-context evidence: flash (auto 512-blocks) vs XLA attention
    fwd+bwd at seq 4096 — the regime the reference cannot reach at all
    (its fused kernels cap at 2048/512)."""
    from apex_tpu.ops.flash_attention import flash_attention

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, h, seq, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, h, seq, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, h, seq, d), jnp.bfloat16)
    dy = jnp.asarray(rng.randn(b, h, seq, d), jnp.bfloat16)

    def make_step(use_pallas):
        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True,
                                  use_pallas=use_pallas)
            return jnp.sum(out.astype(jnp.float32)
                           * dy.astype(jnp.float32))

        def step(carry):
            q, k, v = carry
            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            return (dq.astype(q.dtype), dk.astype(k.dtype),
                    dv.astype(v.dtype))
        return step

    flash_ms, flash_std = _device_loop_ms(make_step(True), (q, k, v), k=10,
                                          reps=3)
    xla_ms, _ = _device_loop_ms(make_step(False), (q, k, v), k=10, reps=3)
    _emit("flash_attention_seq4096_fwd_bwd_ms", flash_ms, "ms",
          xla_ms / flash_ms, xla_ms=round(xla_ms, 3),
          std_ms=round(flash_std, 3), batch=b, heads=h, seq=seq)


def _write_configs():
    with open("BENCH_CONFIGS.json", "w") as f:
        json.dump(_RESULTS, f, indent=1)


def main():
    # default = everything, headline LAST (a driver keeping the final
    # stdout line gets the headline); --headline skips the config benches.
    # Config benches are budgeted so a slow compile can never starve the
    # headline, results are checkpointed to BENCH_CONFIGS.json after every
    # config, and a config failure is recorded in the file (not just
    # printed) via _emit.
    headline_only = "--headline" in sys.argv
    if not headline_only:
        budget_s = 420.0
        t0 = time.perf_counter()
        # the multi-compile configs run LAST, newest first to be starved:
        # sp_ovl (two GPT TP=2 compiles) after the longer-tracked configs
        # above it, remat (FOUR GPT-small train-step compiles) next,
        # gpt_fast (two full hybrid-trainer compiles) after that,
        # gpt_decode_paged (one engine = three AOT compiles) next, and
        # gpt_decode_spec (two engines = seven AOT compiles for
        # the speculative A/B, the newest leg) dead last so a tight
        # budget drops the newest metrics, never the established
        # baseline rows
        for fn in (bench_layernorm, bench_optimizer, bench_gpt,
                   bench_flash_long, bench_dp_accumulate_overlap,
                   bench_gpt_sp_overlap, bench_gpt_remat,
                   bench_gpt_fast, bench_gpt_decode_paged,
                   bench_gpt_decode_spec):
            if time.perf_counter() - t0 > budget_s:
                _emit(fn.__name__, -1.0, "skipped", None,
                      error="config budget exhausted; headline protected")
                continue
            try:
                fn()
            except Exception as e:  # a config bench must not sink the run
                _emit(fn.__name__, -1.0, "error", None, error=str(e))
            _write_configs()
    try:
        bench_headline()
    finally:
        if not headline_only:
            _write_configs()


if __name__ == "__main__":
    main()

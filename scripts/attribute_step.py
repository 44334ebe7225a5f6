#!/usr/bin/env python
"""Per-region step-time attribution of the bench workloads (pyprof).

Generalizes the round-5 ``scripts/profile_rn50.py`` ablation ladder: one
entry point builds the bench-identical train step for ``--model gpt`` or
``--model rn50``, AOT-compiles it, measures the wall step time, prices
every ``named_scope`` region against the chip's roofline
(``apex_tpu.pyprof``), and prints the attribution as a markdown table
(plus JSONL with ``--json``). This is the instrument the "win the
flagship benches" work reads its next move from: the gap between
``measured_step_ms`` and ``modeled_step_ms``, region by region, with
``comm_exposed_ms`` isolating collectives the schedule failed to hide.

Validation: by default the GPT step is built with the layer scan fully
unrolled and the XLA attention path (``use_flash=False``) so XLA's
``cost_analysis`` can count the whole program, and the run FAILS if the
model's total FLOPs disagree with ``costs.flops_budget(compiled)`` by
more than ``--tolerance`` (5%) — the model stays honest against the
compiler. ``--flash`` attributes the real Mosaic-kernel program instead
(Mosaic custom calls report zero cost to XLA, so validation is skipped
and the analytic model is the only source). RN50 has no scanned stacks,
so it validates as-is.

Usage::

    python scripts/attribute_step.py --model gpt
    python scripts/attribute_step.py --model gpt --config '{"hidden_size": 256, "num_layers": 4}'
    python scripts/attribute_step.py --model rn50 --json
    python scripts/attribute_step.py --model gpt --trace-dir /tmp/prof  # measured per-region walls
"""

import argparse
import json
import os
import sys

import numpy as np

# runnable as `python scripts/attribute_step.py` from a checkout: the
# repo root (where apex_tpu/ lives) is the script dir's parent
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _timeit(fn, args, iters, warmup):
    """Mean per-iteration seconds via ``bench._timeit`` — the SAME
    chunked, fetch-RTT-subtracted methodology every bench line uses, so
    ``measured_step_ms`` here is directly comparable to the bench
    ``step_ms`` the attribution budget is read against (a per-iteration
    sync would time the host's dispatch round trip, not the chip)."""
    import bench
    times = bench._timeit(fn, args, max(1, iters), max(1, warmup),
                          chunk=max(1, min(iters, 10)))
    return float(np.mean(times))


def build_gpt(config: dict, flash: bool):
    """The bench config-5 GPT-small train step, built by
    ``bench._gpt_train_step`` itself — the SAME constructor
    :func:`bench.bench_gpt` and the remat sweep use, so the attribution
    instrument cannot drift from the benched program. ``config``
    overrides GPTConfig fields plus ``batch``/``seq``. Returns
    (traced, compiled, args, wrapped).

    Default = VALIDATION mode: XLA attention, fully unrolled layer scan,
    fp32 compute laid over the bench defaults — the configuration XLA's
    cost_analysis can count end to end (a while body is priced once
    regardless of trip count, Mosaic custom calls report zero cost, and
    the CPU backend inflates bf16 transcendental expansions into counted
    flops), so the roofline model is checked against the compiler every
    run. ``--bench`` keeps the bench defaults untouched (bf16 + Mosaic
    flash + scanned stack) with validation off. Per-region FLOP counts
    and shares are dtype-independent; HBM bytes in validation mode price
    the fp32 activation footprint."""
    import jax.numpy as jnp

    import bench

    config = dict(config)
    kw = dict(batch=config.pop("batch", 8), seq=config.pop("seq", 1024))
    # GPTConfig field -> _gpt_train_step parameter renames; every other
    # config key passes through as a cfg_override laid over the bench
    # defaults
    for field, param in (("hidden_size", "hidden"),
                         ("num_layers", "layers"),
                         ("num_attention_heads", "heads"),
                         ("vocab_size", "vocab")):
        if field in config:
            kw[param] = config.pop(field)
    overrides = {} if flash else dict(compute_dtype=jnp.float32,
                                      use_flash=False,
                                      layer_scan_unroll=True)
    overrides.update(config)
    _cfg, args, wrapped, compiled, traced = bench._gpt_train_step(
        **kw, **overrides)
    return traced, compiled, args, wrapped


def build_rn50(config: dict, flash: bool):
    """The bench headline RN50 train step (amp O2, FusedSGD momentum,
    donated buffers); ``config`` overrides ``batch``/``img``/ResNetConfig
    fields. Default = validation mode: fp32 compute and per-leaf FusedSGD
    — same math as the headline, but countable by XLA (the CPU backend
    books the FlatOptimizer's shared flat-buffer computation once per
    leaf slice, inflating its flop count ~100x, and bf16 transcendental
    expansions as flops); ``--bench`` restores the bench-identical
    bf16 + FlatOptimizer program with validation off. Returns (traced,
    compiled, args, wrapped)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.amp.scaler import DynamicLossScale, all_finite
    from apex_tpu.models import ResNet50, ResNetConfig
    from apex_tpu.optimizers import FlatOptimizer, FusedSGD

    config = dict(config)
    batch = config.pop("batch", 256)
    img = config.pop("img", 224)
    kw = dict(num_classes=1000,
              compute_dtype=jnp.bfloat16 if flash else jnp.float32)
    kw.update(config)
    cfg = ResNetConfig(**kw)
    model = ResNet50(cfg)
    params, bn_state = model.init(jax.random.PRNGKey(0))
    sgd = FusedSGD(lr=0.1, momentum=0.9, weight_decay=1e-4)
    opt = FlatOptimizer(sgd) if flash else sgd
    opt_state = opt.init(params)
    scaler = DynamicLossScale(init_scale=2.0 ** 12)
    ls = scaler.init()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(batch, img, img, 3), jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, kw["num_classes"], batch))

    def loss_fn(params, bn_state, scale):
        logits, new_bn = model(params, bn_state, x, training=True)
        onehot = jax.nn.one_hot(labels, kw["num_classes"])
        loss = -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))
        return loss * scale, (loss, new_bn)

    @(lambda f: jax.jit(f, donate_argnums=(0, 1, 2, 3)))
    def step(params, bn_state, opt_state, ls):
        grads, (loss, new_bn) = jax.grad(loss_fn, has_aux=True)(
            params, bn_state, ls.loss_scale)
        finite = all_finite(grads)
        new_ls = scaler.update(ls, finite)
        params, opt_state = opt.step(grads, opt_state, params,
                                     grads_finite=finite,
                                     scale=1.0 / ls.loss_scale)
        return params, new_bn, opt_state, new_ls

    traced = step.trace(params, bn_state, opt_state, ls)
    compiled = traced.lower().compile()

    def wrapped(params, bn_state, opt_state, ls):
        # outputs match the input order exactly, so the _timeit
        # state-threading convention holds without reshuffling
        return compiled(params, bn_state, opt_state, ls)

    return traced, compiled, (params, bn_state, opt_state, ls), wrapped


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--model", choices=("gpt", "rn50"), default="gpt")
    parser.add_argument("--config", default="{}",
                        help="JSON overrides: model fields plus batch/seq "
                             "(gpt) or batch/img (rn50)")
    parser.add_argument("--iters", type=int, default=10)
    parser.add_argument("--warmup", type=int, default=3)
    parser.add_argument("--bench", "--flash", dest="bench",
                        action="store_true",
                        help="attribute the bench-identical program "
                             "(gpt: bf16 + Mosaic flash + scanned stack; "
                             "rn50: bf16 + FlatOptimizer) instead of the "
                             "XLA-countable validation twin; skips "
                             "validation")
    parser.add_argument("--json", action="store_true",
                        help="also print the JSONL form")
    parser.add_argument("--trace-dir", default=None,
                        help="jax.profiler trace dir for measured "
                             "per-region walls")
    parser.add_argument("--trace-steps", type=int, default=1,
                        help="number of steps the --trace-dir capture "
                             "spans (durations divide by it so walls "
                             "are per-step)")
    parser.add_argument("--tolerance", type=float, default=0.05,
                        help="max |modeled/xla - 1| before failing")
    parser.add_argument("--no-validate", action="store_true")
    args = parser.parse_args(argv)
    config = json.loads(args.config)

    build = build_gpt if args.model == "gpt" else build_rn50
    traced, compiled, step_args, wrapped = build(config, args.bench)
    step_time_s = _timeit(wrapped, step_args, args.iters, args.warmup)

    from apex_tpu.pyprof import attribute
    report = attribute(traced, step_time_s, compiled=compiled,
                       trace_dir=args.trace_dir,
                       trace_steps=args.trace_steps)
    print(f"# {args.model} step-time attribution "
          f"({report.spec.name}, {args.iters} iters)")
    print(report.markdown())
    if args.json:
        print(report.json_lines())

    # --bench programs are exactly what XLA cannot count honestly (gpt:
    # Mosaic flash + scanned stack; rn50: FlatOptimizer call inflation)
    validate = not (args.no_validate or args.bench)
    if validate:
        if not report.xla_flops:
            print("validation skipped: backend reports no cost analysis",
                  file=sys.stderr)
            return 0
        delta = report.flops / report.xla_flops - 1.0
        verdict = "ok" if abs(delta) <= args.tolerance else "FAIL"
        print(f"validation {verdict}: modeled flops within {delta:+.2%} "
              f"of costs.flops_budget(compiled) "
              f"(tolerance {args.tolerance:.0%})")
        if verdict == "FAIL":
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

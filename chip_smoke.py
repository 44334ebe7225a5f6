"""chip_smoke.py — does GPT-small still train and serve on the chip?

One process, public entry points only (``apex_tpu.config``,
``apex_tpu.training.GPTHybridTrainer``, ``apex_tpu.serving``), random
weights from a seed, full GPT-small widths. Fails at once without a TPU.

    python chip_smoke.py            # one chip: train, kernels, serve
    python chip_smoke.py --chips 4  # ONLY the tp=2 x dp=2 trainer against
                                    # the same config on a 1-device mesh

Any failed check raises, so the process exits non-zero before the result
line. The last stdout line is the contract:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

import argparse
import functools
import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

# GPT-small: BENCH_TRAIN_CONFIGS["gpt_base"] / BENCH_DECODE_CONFIGS (bench.py)
MODEL = dict(vocab_size=32768, hidden_size=768, num_layers=12,
             num_attention_heads=12, max_position_embeddings=1024)
SEQ = 1024
MICROBATCHES, MICRO_BATCH = 2, 4          # 2 x 4 x 1024 tokens per step
TRAIN_STEPS, SHARDED_STEPS = 5, 3
SERVE = dict(max_seqs=8, max_len=1024, prefill_len=128)
# the pool an engine sizes for SERVE by itself: blocks of gcd(128, 128)
# tokens, 8 slots x 8 blocks + the null block
BLOCK_SIZE, NUM_BLOCKS = 128, 65
SPECULATE_K = 4
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 16, (16, 128), (8, 64)
REF_TOKENS = 16                # request 0's tokens checked free-running
# docs/SERVING.md "Tolerances": kernel decode vs one-shot forward agree
# within 0.05 logit units at bf16 — bitwise identity is not the contract
LOGIT_TOL = 0.05
# an engine may part from the reference's greedy stream only where the
# reference's own logits for the two tokens are this close (largest gap
# seen at a fork: 0.0095, PERF.md PR 22)
FORK_TOL = 0.02
# the bf16 decode kernel vs the same function's XLA path on randn inputs
# (tests/test_paged.py: atol 2e-2)
KERNEL_TOL = 2e-2
SEED = 0


def check(ok, what):
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def say(**fields):
    print(json.dumps(fields), flush=True)


def block(tree):
    jax.block_until_ready(tree)
    return tree


def train_config(tp, dp):
    from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                                 ParallelConfig, TrainConfig)
    mb = MICRO_BATCH // dp                # the SAME global batch on any dp
    return TrainConfig(
        model=ModelConfig(name="gpt", **MODEL),
        parallel=ParallelConfig(tensor_model_parallel_size=tp),
        batch=BatchConfig(global_batch_size=MICROBATCHES * mb * dp,
                          micro_batch_size=mb),
        optimizer=OptimizerConfig(name="adam", lr=1e-4, weight_decay=0.01),
        opt_level="O2", half_dtype="bfloat16", seed=SEED)


def seeded_batch():
    rng = np.random.RandomState(SEED)
    shape = (MICROBATCHES, MICRO_BATCH, SEQ)
    return (rng.randint(0, MODEL["vocab_size"], shape).astype(np.int32),
            rng.randint(0, MODEL["vocab_size"], shape).astype(np.int32))


def run_trainer(devices, tp, steps, *, with_metrics):
    """Build the trainer on ``devices``, AOT-compile the donated step, take
    ``steps`` steps on the seeded batch. Returns what the phases assert on."""
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    cfg = train_config(tp, len(devices) // tp)
    mesh = cfg.initialize_mesh(devices=devices)
    try:
        trainer = GPTHybridTrainer(cfg, mesh)
        state = trainer.init_state(jax.random.PRNGKey(SEED))
        tokens, targets = seeded_batch()
        t0 = time.perf_counter()
        compiled = trainer.jit_train_step(donate=True).lower(
            *state, tokens, targets).compile()
        compile_s = time.perf_counter() - t0
        text = compiled.as_text()

        losses, step_ms = [], []
        for _ in range(steps):
            t0 = time.perf_counter()
            loss, *state = block(compiled(*state, tokens, targets))
            step_ms.append((time.perf_counter() - t0) * 1e3)
            losses.append(float(loss))
        param_devices = set()
        for leaf in jax.tree_util.tree_leaves(state[0]):
            param_devices |= leaf.sharding.device_set

        metrics = None
        if with_metrics:
            # the instrumented step a trainer with a StepReporter runs
            *_, m = block(trainer.jit_train_step(
                with_metrics=True, donate=False)(*state, tokens, targets))
            metrics = m.as_floats()
        return dict(text=text, compile_s=compile_s, losses=losses,
                    step_ms=step_ms, metrics=metrics,
                    n_param_devices=len(param_devices))
    finally:
        parallel_state.destroy_model_parallel()


def train_phase():
    out = run_trainer(jax.devices()[:1], tp=1, steps=TRAIN_STEPS,
                      with_metrics=True)
    losses = out["losses"]
    check("tpu_custom_call" in out["text"],
          "compiled train step holds no Pallas kernel (tpu_custom_call)")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a repeated batch: {losses}")
    watched = {k: v for k, v in out["metrics"].items()
               if k.startswith(("amp/", "optim/"))}
    check(any(k.startswith("amp/") for k in watched)
          and any(k.startswith("optim/") for k in watched),
          f"train_step_with_metrics returned no amp/* or optim/*: "
          f"{sorted(out['metrics'])}")
    check(all(np.isfinite(list(watched.values()))),
          f"non-finite step metric: {watched}")
    stats = jax.devices()[0].memory_stats() or {}
    say(phase="train", losses=losses, compile_s=out["compile_s"],
        step_ms_after_warmup=out["step_ms"][1:],
        tokens_per_step=MICROBATCHES * MICRO_BATCH * SEQ,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"), metrics=watched)


def sharded_phase():
    """tp=2 x dp=2 on four chips against the same config and batch on a
    1-device mesh, in this one process."""
    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    one = run_trainer(devices[:1], tp=1, steps=SHARDED_STEPS,
                      with_metrics=False)
    four = run_trainer(devices, tp=2, steps=SHARDED_STEPS,
                       with_metrics=False)
    check(all(np.isfinite(one["losses"] + four["losses"])),
          f"non-finite loss: 1 chip {one['losses']}, 4 chips "
          f"{four['losses']}")
    rel = abs(four["losses"][0] - one["losses"][0]) / abs(one["losses"][0])
    check(rel <= 2e-2, f"step-1 loss differs by {rel:.3g} relative: 4 chips "
                       f"{four['losses'][0]} vs 1 chip {one['losses'][0]}")
    check(four["n_param_devices"] == 4,
          f"parameter shards sit on {four['n_param_devices']} device(s), "
          "not 4")
    check("all-reduce" in four["text"] or "reduce-scatter" in four["text"],
          "4-chip step holds neither an all-reduce nor a reduce-scatter")
    check("tpu_custom_call" in four["text"],
          "4-chip step holds no Pallas kernel (tpu_custom_call)")
    say(phase="sharded_train", mesh="tp=2 x dp=2",
        losses_4chip=four["losses"], losses_1chip=one["losses"],
        step1_rel_diff=rel, compile_s_4chip=four["compile_s"],
        step_ms_after_warmup_4chip=four["step_ms"][1:],
        step_ms_after_warmup_1chip=one["step_ms"][1:],
        param_devices=four["n_param_devices"])


def kernel_phase():
    """The decode kernel's arithmetic at the serving shape: the Pallas
    kernel against the same function's XLA path (``use_pallas=False``) on
    one seeded pool — ``q_len`` 1 (decode) and ``SPECULATE_K + 1``
    (verify), bf16 and int8 KV. The serve phase's argmax of a
    random-weight model barely moves with the attention context; this
    does."""
    from apex_tpu.ops.flash_attention import paged_decode_attention
    S, T = SERVE["max_seqs"], SERVE["max_len"]
    H = MODEL["num_attention_heads"]
    D = MODEL["hidden_size"] // H
    bs, nb = BLOCK_SIZE, NUM_BLOCKS
    rng = np.random.RandomState(SEED + 2)
    # every slot's blocks scattered through the pool, never block 0 (null)
    tables = rng.permutation(np.arange(1, nb)).reshape(S, T // bs)
    tables = jnp.asarray(tables, jnp.int32)
    # empty, single, around a block edge, mid-stripe, nearly full
    lengths = [0, 1, bs - 1, bs, bs + 1, 5 * bs, T - 24, T - 1]
    check(len(lengths) == S and max(lengths) < T, "kernel_phase's cursors "
          f"are written for 8 slots of >= 6 blocks, not {S} x {T // bs}")
    lengths = jnp.asarray(lengths, jnp.int32)

    def quantize(x):           # per-(position, head) symmetric int8
        scale = np.maximum(np.abs(x).max(-1) / 127.0, 1e-8)
        q = np.clip(np.round(x / scale[..., None]), -127, 127)
        return (jnp.asarray(q.reshape(nb, bs, H * D), jnp.int8),
                jnp.asarray(scale.transpose(0, 2, 1), jnp.float32))

    # the pool as PagedKVCache stores it: token-major, the heads fused
    # into the lane axis; one layer here, stacked as the kernel takes it
    kf, vf = (rng.randn(nb, bs, H, D).astype(np.float32) for _ in "kv")
    pools = {"bf16": (jnp.asarray(kf.reshape(nb, bs, H * D), jnp.bfloat16),
                      jnp.asarray(vf.reshape(nb, bs, H * D), jnp.bfloat16),
                      None, None),
             "int8": tuple(x for pair in zip(quantize(kf), quantize(vf))
                           for x in pair)}

    def paged(q, kp, vp, *rest, ksc=None, vsc=None, use_pallas):
        return paged_decode_attention(
            q, kp[None], vp[None], 0, *rest,
            k_scale=None if ksc is None else ksc[None],
            v_scale=None if vsc is None else vsc[None],
            use_pallas=use_pallas)

    diffs = {}
    for kv, (kp, vp, ksc, vsc) in pools.items():
        for q_len in (1, SPECULATE_K + 1):
            shape = (S, H, D) if q_len == 1 else (S, H, q_len, D)
            q, k_new, v_new = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
                               for _ in "qkv")
            args = (q, kp, vp, tables, lengths, k_new, v_new)
            case = f"paged/q{q_len}/{kv}"
            outs = {}
            for use in (True, False):
                prog = jax.jit(functools.partial(
                    paged, ksc=ksc, vsc=vsc,
                    use_pallas=use)).lower(*args).compile()
                check(("tpu_custom_call" in prog.as_text()) == use,
                      f"{case}: use_pallas={use} compiled the other "
                      "attention path")
                outs[use] = np.asarray(block(prog(*args)), np.float32)
            check(np.isfinite(outs[True]).all(),
                  f"{case}: the kernel returned a non-finite value")
            diffs[case] = float(np.abs(outs[True] - outs[False]).max())
            check(diffs[case] <= KERNEL_TOL,
                  f"{case}: kernel and XLA path differ by "
                  f"{diffs[case]:.4g} (tolerance {KERNEL_TOL})")
    say(phase="kernels", slots=S, heads=H, max_len=T, head_dim=D,
        block_size=bs, max_abs_diff_kernel_vs_xla=diffs,
        worst=max(diffs.values()))


def seeded_requests():
    from apex_tpu.serving import Request
    rng = np.random.RandomState(SEED + 1)
    return [Request(
        prompt=rng.randint(1, MODEL["vocab_size"],
                           size=rng.randint(PROMPT_LEN[0],
                                            PROMPT_LEN[1] + 1)).tolist(),
        max_new_tokens=int(rng.randint(NEW_TOKENS[0], NEW_TOKENS[1] + 1)),
        temperature=0.0, request_id=i) for i in range(N_REQUESTS)]


class Reference:
    """Plain full-sequence forward: ``GPTConfig(use_flash=False)``, no
    cache, no kernel — ONE padded ``(N_REQUESTS, width)`` program."""

    def __init__(self, params):
        from apex_tpu.models import GPTConfig, GPTModel
        self.params = params
        self.width = PROMPT_LEN[1] + NEW_TOKENS[1]
        model = GPTModel(GPTConfig(use_flash=False, **MODEL))
        self.logits = jax.jit(lambda p, toks: model(p, toks)).lower(
            params, self._padded([])).compile()
        check("tpu_custom_call" not in self.logits.as_text(),
              "the reference forward holds a kernel")

    def _padded(self, rows):
        out = np.zeros((N_REQUESTS, self.width), np.int32)
        for i, row in enumerate(rows):
            out[i, :len(row)] = row
        return out

    def greedy(self, prompt, n):
        """Free-running greedy continuation (causal: right padding is
        invisible to the positions read)."""
        seq = list(prompt)
        for _ in range(n):
            logits = self.logits(self.params, self._padded([seq]))
            seq.append(int(jnp.argmax(logits[0, len(seq) - 1])))
        return seq[len(prompt):]

    def teacher_forced(self, requests, streams):
        """The reference's logits at every position that emitted a token,
        given the tokens before it: ``{request_id: (n_tokens, vocab)}``.
        One batched forward."""
        rows = [list(r.prompt) + list(streams[r.request_id])
                for r in requests]
        logits = np.asarray(self.logits(self.params, self._padded(rows)),
                            np.float32)
        return {r.request_id: logits[i, len(r.prompt) - 1:
                                     len(r.prompt) - 1
                                     + len(streams[r.request_id])]
                for i, r in enumerate(requests)}


def regrets(ref_logits, stream):
    """How far below the reference's best logit each emitted token's own
    reference logit sits (0 = the token IS the reference argmax)."""
    return ref_logits.max(axis=-1) - ref_logits[np.arange(len(stream)),
                                                stream]


def timed_ms(fn, n):
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def serve_phase():
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.observability.registry import MetricsRegistry
    from apex_tpu.serving import ServingEngine, SlotScheduler

    model = GPTModel(GPTConfig(**MODEL))
    params = block(model.init(jax.random.PRNGKey(SEED)))
    reference = Reference(params)
    requests = seeded_requests()

    # the pool left to the engine: every slot can reach max_len
    engines = {
        "paged": lambda: ServingEngine(
            model, params, cache_dtype=jnp.bfloat16, **SERVE),
        "paged_spec": lambda: ServingEngine(
            model, params, cache_dtype=jnp.bfloat16,
            speculate_k=SPECULATE_K, **SERVE),
    }
    free = reference.greedy(requests[0].prompt, REF_TOKENS)
    forks = {}
    for name, build in engines.items():
        t0 = time.perf_counter()
        engine = build()
        compile_s = time.perf_counter() - t0
        check((engine.block_size, engine.num_blocks)
              == (BLOCK_SIZE, NUM_BLOCKS),
              f"{name}: the default pool is {engine.num_blocks} blocks of "
              f"{engine.block_size}, the kernel phase checked "
              f"{NUM_BLOCKS} of {BLOCK_SIZE}")
        paths = engine.attention_paths()
        check(all(p == "pallas" for p in paths.values()),
              f"{name}: an AOT program fell back to XLA attention: {paths}")
        registry = MetricsRegistry()
        sched = SlotScheduler(engine, registry=registry,
                              speculate_k=engine.speculate_k)
        t0 = time.perf_counter()
        done = sched.run(seeded_requests(), no_recompile=True)
        run_s = time.perf_counter() - t0
        check(sorted(done) == list(range(N_REQUESTS)),
              f"{name}: completions {sorted(done)}")
        reasons = {c.finish_reason for c in done.values()}
        check(reasons <= {"length", "eos"},
              f"{name}: finish reasons {reasons}")
        streams = {i: c.tokens for i, c in done.items()}
        check(all(len(streams[r.request_id]) == r.max_new_tokens
                  for r in requests), f"{name}: a stream is short")
        ref_logits = reference.teacher_forced(requests, streams)
        regret = {i: regrets(ref_logits[i], streams[i]) for i in done}
        every = np.concatenate([regret[i] for i in sorted(done)])
        worst, exact, total = (float(every.max()),
                               int((every == 0).sum()), len(every))
        check(worst <= LOGIT_TOL,
              f"{name}: an emitted token sits {worst:.4f} logit units "
              f"below the reference's best (tolerance {LOGIT_TOL})")
        # The reference's greedy stream — or, where the engine parts from
        # it, it parts at a position the REFERENCE itself calls a tie (its
        # logits for the two tokens within FORK_TOL). Up to a request's
        # first token that is not the reference's argmax the two streams
        # are one, so that token is where they part and its regret is the
        # gap. The paths reduce in different orders (one softmax vs KV
        # blocks of 128, 1 vs k+1 query rows), greedy argmax in bf16 is
        # tie-sensitive, and after a fork the stream answers to the
        # reference token by token (checked above).
        forks[name] = []
        for i in sorted(done):
            parted = np.flatnonzero(regret[i] > 0)
            if not len(parted):
                continue
            at, gap = int(parted[0]), float(regret[i][parted[0]])
            check(gap <= FORK_TOL,
                  f"{name} and the reference's greedy stream part at token "
                  f"{at} of request {i}, where the reference is NOT tied "
                  f"(gap {gap:.4f}, tolerance {FORK_TOL})")
            forks[name].append(dict(request=i, token=at, reference_gap=gap))
        # ... and request 0's, run free, is that stream as far as it goes
        same = next((p for p, (a, b) in enumerate(zip(free, streams[0]))
                     if a != b), min(len(free), len(streams[0])))
        first = next((f["token"] for f in forks[name] if f["request"] == 0),
                     len(streams[0]))
        check(same == min(first, len(free)),
              f"{name}: request 0 leaves the free-running reference at "
              f"token {same}, its teacher-forced logits say {first}")
        counters = {k: v for k, v in registry.snapshot().items()
                    if k.startswith("serve/") and "_bucket_le_" not in k
                    and not k.endswith(("_ms_sum", "_ms_count"))}
        line = dict(phase="serve", engine=name, attention=paths,
                    build_and_compile_s=compile_s, run_s=run_s,
                    tokens=total, reference_argmax_matches=exact,
                    worst_reference_regret=worst, counters=counters)
        if name == "paged":
            # the engine is idle again: time the two programs directly
            # (both return host values, so each call is a full round trip)
            prompt = requests[0].prompt

            def prefill_once():
                engine.prefill(prompt, 0)
                check(engine.last_admit.prefill, "a timed prefill was "
                      "served from the prefix index")
                engine.release_slot(0)

            line["prefill_and_release_ms"] = timed_ms(prefill_once, 5)
            toks = np.zeros(SERVE["max_seqs"], np.int32)
            temps = np.zeros(SERVE["max_seqs"], np.float32)
            line["decode_ms"] = timed_ms(
                lambda: engine.decode(toks, temps), 20)
        say(**line)
        del engine, sched

    say(phase="serve", request0_reference_greedy=free,
        forks_from_reference_greedy=forks)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tp=2 x dp=2 trainer phase")
    args = ap.parse_args(argv)

    first = jax.devices()[0]
    if first.platform != "tpu":
        sys.exit(f"chip_smoke needs a TPU; JAX found {first.platform!r} "
                 f"({first.device_kind!r})")
    from apex_tpu.utils.compile_cache import enable_compile_cache
    say(phase="start", cache_dir=enable_compile_cache(),
        jax=jax.__version__, devices=len(jax.devices()))

    if args.chips == 4:
        sharded_phase()
    else:
        train_phase()
        kernel_phase()
        serve_phase()
    print(json.dumps({"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()

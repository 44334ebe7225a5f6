"""Why greedy streams part between the serving engines on the chip.

``chip_smoke.py`` finds the dense, paged and speculative engines emitting
different tokens at positions where the plain reference is nearly tied
(PERF.md, PR 22). This probe separates the candidate causes. One chip, one
process, the requests and weights of ``chip_smoke.py``:

- ``kernels``:  the engines as ``chip_smoke.py`` builds them.
- ``xla``:      the same engines on ``GPTConfig(use_flash=False)`` — no
                kernel anywhere, so no KV-block reduction order; the paged
                engine gathers its blocks into the dense layout and runs
                the dense math.
- ``block128``: the dense kernel streaming 128-wide cache blocks like the
                paged one (it picks 512 at max_len 1024). Steered from
                here; the program has no such option.
- ``f32cache``: kernels, fp32 KV cache — no rounding at the cache store.
- ``gemm_rows``: one set of 8 rows through the model's GEMM shapes alone
                and inside 40 rows (decode sees 8 rows, verify 8 x 5).

    python scripts/chip_fork_probe.py     # needs a TPU; prints JSON lines
"""

import importlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke as cs  # noqa: E402


def streams_of(engine):
    from apex_tpu.serving import SlotScheduler
    sched = SlotScheduler(engine, speculate_k=engine.speculate_k)
    done = sched.run(cs.seeded_requests(), no_recompile=True)
    return {i: c.tokens for i, c in done.items()}


def parted(a, b):
    """``[(request, first differing token)]`` between two runs."""
    return [(i, next((p for p, (x, y) in enumerate(zip(a[i], b[i]))
                      if x != y), min(len(a[i]), len(b[i]))))
            for i in sorted(a) if a[i] != b[i]]


def engine_runs(model, params, cache_dtype, names):
    from apex_tpu.serving import PagedServingEngine, ServingEngine
    kw = dict(cache_dtype=cache_dtype, **cs.SERVE)
    build = {
        "dense": lambda: ServingEngine(model, params, **kw),
        "paged": lambda: PagedServingEngine(model, params, **kw, **cs.PAGED),
        "dense_spec": lambda: ServingEngine(
            model, params, speculate_k=cs.SPECULATE_K, **kw),
    }
    out = {}
    for name in names:
        engine = build[name]()
        out[name] = streams_of(engine), engine.attention_paths()
        del engine
    return out


def gemm_rows(model, params):
    """Does a row's GEMM result on the chip depend on how many rows ride
    with it? bf16 operands, fp32 accumulation, the model's own contraction
    (``dot_general`` against an ``(out, in)`` weight)."""
    rng = np.random.RandomState(cs.SEED + 3)
    hidden = cs.MODEL["hidden_size"]
    rows8 = cs.SERVE["max_seqs"]
    rows40 = rows8 * (cs.SPECULATE_K + 1)

    def gemm(x, w):
        return jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.bfloat16)

    report = {}
    shapes = {"qkv": (hidden, 3 * hidden), "mlp_in": (hidden, 4 * hidden),
              "mlp_out": (4 * hidden, hidden)}
    for name, (k, n) in shapes.items():
        x = jnp.asarray(rng.randn(rows40, k), jnp.bfloat16)
        w = jnp.asarray(rng.randn(n, k) * 0.02, jnp.bfloat16)
        alone = np.asarray(jax.jit(gemm)(x[:rows8], w), np.float32)
        among = np.asarray(jax.jit(gemm)(x, w)[:rows8], np.float32)
        report[name] = dict(
            elements_that_differ=int((alone != among).sum()),
            of=int(alone.size),
            max_abs_diff=float(np.abs(alone - among).max()))
    # the vocab head through the model's own method, fp32 out
    x = jnp.asarray(rng.randn(rows40, 1, hidden), jnp.bfloat16)
    head = jax.jit(model.logits)
    alone = np.asarray(head(params, x[:rows8]))
    among = np.asarray(head(params, x)[:rows8])
    report["head"] = dict(
        elements_that_differ=int((alone != among).sum()), of=int(alone.size),
        max_abs_diff=float(np.abs(alone - among).max()))
    return report


def probe():
    from apex_tpu.models import GPTConfig, GPTModel

    model = GPTModel(GPTConfig(**cs.MODEL))
    params = cs.block(model.init(jax.random.PRNGKey(cs.SEED)))
    bf16 = jnp.bfloat16
    all_three = ("dense", "paged", "dense_spec")

    def report(variant, runs, base):
        cs.say(variant=variant,
               attention={n: paths for n, (_, paths) in runs.items()},
               requests_parted_from=base[0],
               parted={n: parted(base[1], s) for n, (s, _) in runs.items()})

    kernels = engine_runs(model, params, bf16, all_three)
    dense = ("kernels/dense", kernels["dense"][0])
    report("kernels", kernels, dense)

    plain = GPTModel(GPTConfig(use_flash=False, **cs.MODEL))
    xla = engine_runs(plain, params, bf16, all_three)
    report("xla", xla, ("xla/dense", xla["dense"][0]))
    report("xla_vs_kernels", xla, dense)

    fa = importlib.import_module("apex_tpu.ops.flash_attention")
    auto_block = fa._auto_block
    fa._auto_block = lambda seq, choices=None: auto_block(seq, (128,))
    try:
        block128 = engine_runs(model, params, bf16, ("dense",))
    finally:
        fa._auto_block = auto_block
    report("block128_vs_dense512", block128, dense)
    report("block128_vs_paged", block128,
           ("kernels/paged", kernels["paged"][0]))

    f32 = engine_runs(model, params, jnp.float32, all_three)
    report("f32cache", f32, ("f32cache/dense", f32["dense"][0]))

    cs.say(variant="gemm_rows", rows=[8, 40], report=gemm_rows(model, params))


def main():
    first = jax.devices()[0]
    if first.platform != "tpu":
        sys.exit(f"chip_fork_probe needs a TPU; JAX found {first.platform!r}")
    from apex_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    probe()
    cs.say(probe_ok=True, device=first.device_kind)


if __name__ == "__main__":
    main()

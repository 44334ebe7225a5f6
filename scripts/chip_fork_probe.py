"""Why greedy streams part between the plain and the speculative engine
on the chip.

``chip_smoke.py`` finds the engine emitting, with and without
speculation, different tokens at positions where the plain reference is
nearly tied (PERF.md, PR 22). This probe separates the candidate causes.
One chip, one process, the requests and weights of ``chip_smoke.py``:

- ``kernels``:  the engines as ``chip_smoke.py`` builds them.
- ``xla``:      the same engines on ``GPTConfig(use_flash=False)`` — no
                kernel anywhere, so no KV-block reduction order: the
                blocks are gathered slot-major and scored in one pass.
- ``f32cache``: kernels, fp32 KV cache — no rounding at the cache store.
- ``gemm_rows``: one set of 8 rows through the model's GEMM shapes alone
                and inside 40 rows (decode sees 8 rows, verify 8 x 5).

    python scripts/chip_fork_probe.py     # needs a TPU; prints JSON lines
"""

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


def engine_runs(model, params, cache_dtype):
    from apex_tpu.serving import ServingEngine
    kw = dict(cache_dtype=cache_dtype, **cs.SERVE)
    out = {}
    for name, k in (("paged", 0), ("paged_spec", cs.SPECULATE_K)):
        engine = ServingEngine(model, params, speculate_k=k, **kw)
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

    def report(variant, runs, base):
        cs.say(variant=variant,
               attention={n: paths for n, (_, paths) in runs.items()},
               requests_parted_from=base[0],
               parted={n: parted(base[1], s) for n, (s, _) in runs.items()})

    kernels = engine_runs(model, params, bf16)
    paged = ("kernels/paged", kernels["paged"][0])
    report("kernels", kernels, paged)

    plain = GPTModel(GPTConfig(use_flash=False, **cs.MODEL))
    xla = engine_runs(plain, params, bf16)
    report("xla", xla, ("xla/paged", xla["paged"][0]))
    report("xla_vs_kernels", xla, paged)

    f32 = engine_runs(model, params, jnp.float32)
    report("f32cache", f32, ("f32cache/paged", f32["paged"][0]))

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

"""Time the causal flash kernels alone on the chip, tile by tile.

    chiprun -- python scripts/flash_tile_sweep.py [--parent DIR] [--quick]

One process, one chip. For each ``(block_q, block_k, sub_q, sub_k)`` it
compiles the forward and the backward kernel at the train cell's shape
(64 batch-heads x 1,024 x 64, bf16, causal) and prints the ms a call of
each; then the forward at gpt2-large's prefill bucket and at the pattern
model's window buckets with the tiles ``_auto_block`` picks. ``--parent
DIR`` times the kernels of another checkout's
``apex_tpu/ops/flash_attention.py`` first (where that has a dq and a dk/dv
kernel, as before PR 33, each is timed with the other dropped by the
compiler, and both together), for the before/after table of ``PERF.md``. JSON lines on stdout, the table in
``chiprun_out/flash_tile_sweep.jsonl``.
"""
import argparse
import importlib
import importlib.util
import inspect
import itertools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
fa = importlib.import_module("apex_tpu.ops.flash_attention")
STRIPS = fa._STRIPS

CALLS, REPEATS = 40, 3
TILES = "1024,1024,512,512;512,512,512,512;1024,1024,256,256"


def ms_a_call(fn, *args):
    fn = jax.jit(fn)
    jax.block_until_ready(fn(*args))
    best = []
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = None
        for _ in range(CALLS):
            out = fn(*args)
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t0) / CALLS * 1e3)
    return round(float(np.median(best)), 4)


def inputs(bh, sq, sk, d, bh_kv=None, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    mk = lambda k, n, s: (0.5 * jax.random.normal(k, (n, s, d))).astype(
        jnp.bfloat16)
    return (mk(keys[0], bh, sq), mk(keys[1], bh_kv or bh, sk),
            mk(keys[2], bh_kv or bh, sk), mk(keys[3], bh, sq))


def emit(out, **row):
    line = json.dumps(row)
    print(line, flush=True)
    out.write(line + "\n")
    out.flush()


def train_kernels(mod, fwd_kw, bwd_kw, q, k, v, do, h=16):
    """``(fwd, args)`` and ``(bwd, args)`` of ``mod`` at one tiling."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    common = dict(scale=float(scale), causal=True, dropout_rate=0.0)

    def fwd(q, k, v):
        return mod._fwd_pallas(q, k, v, None, None, None, h, **fwd_kw,
                               **common)

    out, lse = jax.jit(fwd)(q, k, v)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)

    def bwd(q, k, v, do, lse, delta):
        return mod._bwd_pallas(q, k, v, None, None, None, h, do, lse, delta,
                               **bwd_kw, **common)
    return (fwd, (q, k, v)), (bwd, (q, k, v, do, lse, delta))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent")
    ap.add_argument("--tiles", default=TILES,
                    help="block_q,block_k,sub_q,sub_k;... to try")
    ap.add_argument("--strips", default=str(fa._STRIPS),
                    help="values of _STRIPS to try, comma-separated")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes on whatever backend: checks the "
                         "script, times nothing worth reading")
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"flash_tile_sweep needs a TPU; JAX found {dev.platform!r}")
    global CALLS, REPEATS
    if args.rehearse:
        CALLS, REPEATS = 1, 1
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/flash_tile_sweep.jsonl", "a")
    emit(out, device=dev.device_kind, calls=CALLS, repeats=REPEATS)
    q, k, v, do = inputs(*((2, 256, 256, 64) if args.rehearse
                           else (64, 1024, 1024, 64)))
    parent = grads = None

    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_fa", os.path.join(args.parent,
                                      "apex_tpu/ops/flash_attention.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        for bq, bk in ((512, 512), (256, 256), (128, 128), (256, 512)):
            if bq > q.shape[1] or bk > k.shape[1]:
                continue
            kw = dict(block_q=bq, block_k=bk)
            if "tile" in inspect.signature(parent._fwd_pallas).parameters:
                kw = dict(tile=parent._auto_block(q.shape[1], k.shape[1],
                                                    q.shape[2], bq, bk))
            f, b = train_kernels(parent, kw, kw, q, k, v, do)
            if grads is None:
                grads = jax.jit(b[0])(*b[1])
            emit(out, what="parent", tile=[bq, bk],
                 fwd_ms=ms_a_call(f[0], *f[1]),
                 dq_ms=ms_a_call(lambda *a: b[0](*a)[0], *b[1]),
                 dkv_ms=ms_a_call(lambda *a: b[0](*a)[1:], *b[1]),
                 bwd_ms=ms_a_call(b[0], *b[1]))

    # the reference the change is held to on the chip, once
    ref = fa.mha_reference(q[None], k[None], v[None], causal=True)[0]
    tiles = [tuple(map(int, t.split(","))) for t in args.tiles.split(";")]
    if args.rehearse:
        tiles = [(256, 256, 128, 128), (256, 256, 256, 256)]
    for strips, tile in itertools.product(map(int, args.strips.split(",")),
                                          tiles):
        fa._STRIPS = strips
        row = dict(what="change", tile=list(tile), strips=strips)
        try:
            f, b = train_kernels(fa, dict(tile=tile), dict(tile=tile),
                                 q, k, v, do)
            o = jax.jit(f[0])(*f[1])[0]
            row["fwd_err"] = round(float(jnp.max(jnp.abs(
                o.astype(jnp.float32) - ref.astype(jnp.float32)))), 5)
            if grads is not None:   # against the parent's own kernels
                got = jax.jit(b[0])(*b[1])
                row["bwd_err"] = round(max(float(jnp.max(jnp.abs(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                    for a, b in zip(got, grads)), 5)
            row["fwd_ms"] = ms_a_call(f[0], *f[1])
            row["bwd_ms"] = ms_a_call(b[0], *b[1])
        except Exception as e:  # a tile the compiler refuses is a finding
            row["error"] = str(e)[:300]
        emit(out, **row)

    # the other cells' shapes, with the tiles the chooser picks
    fa._STRIPS = STRIPS

    def auto_fwd(mod, name, bh, s, d, h, kvh, window, block=None):
        qq, kk, vv, _ = inputs(bh, s, s, d, bh_kv=bh // h * kvh)
        b = bh // h

        def f(q, k, v):
            return mod.flash_attention(
                q.reshape(b, h, s, d), k.reshape(b, kvh, s, d),
                v.reshape(b, kvh, s, d), causal=True, window=window,
                block_q=block, block_k=block)
        got = jax.jit(f)(qq, kk, vv)
        want = fa.mha_reference(qq.reshape(b, h, s, d),
                                kk.reshape(b, kvh, s, d),
                                vv.reshape(b, kvh, s, d), causal=True,
                                window=window)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        emit(out, what=name, of="change" if mod is fa else "parent",
             shape=[bh, s, d], window=window,
             tile=list(fa._auto_block(s, s, d, block, block))
             if mod is fa else block,
             fwd_ms=ms_a_call(f, qq, kk, vv), fwd_err=round(err, 5))

    for mod in filter(None, (parent, fa)):
        if args.rehearse:
            auto_fwd(mod, "pattern window", 4, 512, 128, 4, 1, 300)
            continue
        auto_fwd(mod, "gpt2-large prefill", 20, 512, 64, 20, 20, None)
        for s in (1024, 2048, 4096, 8192):
            auto_fwd(mod, "pattern window", 16, s, 128, 16, 1, 4096)
        auto_fwd(mod, "pattern full", 16, 4096, 128, 16, 1, None)
        if mod is fa:      # the other grid tile, by the public override
            for s in (1024, 4096, 8192):
                auto_fwd(mod, "pattern window", 16, s, 128, 16, 1, 4096, 512)


if __name__ == "__main__":
    main()

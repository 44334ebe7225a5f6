"""Time the paged decode kernel alone on the chip, load by load.

    chiprun -- python scripts/paged_decode_sweep.py [--parent DIR] [--quick]

One process, one chip. At the three serving configurations' shapes
(gpt2-large: 32 slots x 20 heads x 64 over 8 blocks a slot; command-a's
full and window kinds: 32 x 16 heads on 1 KV head of 128 over 68 blocks,
window 4,096; Nemotron's attention layer: 64 x 8 on 1 x 128 over 32) it
runs the kernel as a layer scan does, 36 calls a program with the walk
built once outside, for 1, 6, 12 and all slots live at contexts of 1, 2
and the full span of blocks, and reads the kernel's own device time from a
profiler trace (the events named ``paged_decode_attention``; the XLA ops
round it are left out). A least-squares line through a shape's loads gives
the cost a call whatever the load and the cost a live block. ``--parent
DIR`` times ``_paged_decode_pallas`` of another checkout's
``apex_tpu/ops/flash_attention.py`` first (the ``max_seqs x blocks a slot``
grid, before PR 36), for the before/after table of ``PERF.md``, and says
load by load whether the two forms' summed outputs are the same bits. JSON
lines on stdout, the table in ``chiprun_out/paged_decode_sweep.jsonl``.
"""
import argparse
import glob
import importlib
import importlib.util
import inspect
import json
import os
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
fa = importlib.import_module("apex_tpu.ops.flash_attention")
from benchmark import trace_reduce  # noqa: E402

CALLS, REPEATS, BLOCK = 36, 3, 128
# slots, query heads, KV heads, head dim, blocks a slot, window, pool blocks
# a slot (a window kind's pool holds its window's span only)
SHAPES = {
    "gpt2-large": (32, 20, 20, 64, 8, None, 8),
    "command-a.full": (32, 16, 1, 128, 68, None, 68),
    "command-a.window": (32, 16, 1, 128, 68, 4096, 33),
    "nemotron": (64, 8, 1, 128, 32, None, 32),
}


def inputs(shape, seed=0):
    S, h, hkv, d, per_slot, _, held = SHAPES[shape]
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    pool = (2, S * held + 1, BLOCK, hkv * d)
    mk = lambda k, s: (0.5 * jax.random.normal(k, s)).astype(jnp.bfloat16)
    tables = 1 + np.arange(S)[:, None] * held + np.arange(per_slot) % held
    return (mk(keys[0], (S, h, 1, d)), mk(keys[1], pool), mk(keys[2], pool),
            jnp.asarray(tables, jnp.int32))


def layer_scan(mod, shape):
    """The kernel as a decode step runs it: ``CALLS`` calls, the layer
    index the scan's, what the calls share made once outside."""
    _, _, _, d, per_slot, window, _ = SHAPES[shape]
    takes_work = "work" in inspect.signature(
        mod._paged_decode_pallas).parameters

    def program(q, kp, vp, tables, lengths):
        rest = (None, None) + ((mod.paged_work_list(
            lengths, BLOCK, per_slot, window),) if takes_work else ())

        def one(acc, layer):
            out, lse = mod._paged_decode_pallas(
                q, kp, vp, layer % kp.shape[0], tables, lengths, *rest,
                scale=d ** -0.5, mean_context=None, window=window)
            return acc + out.astype(jnp.float32), None
        return jax.lax.scan(one, jnp.zeros(q.shape, jnp.float32),
                            jnp.arange(CALLS, dtype=jnp.int32))[0]
    return jax.jit(program)


def loads(shape, quick):
    S, _, _, _, per_slot, window, _ = SHAPES[shape]
    span = per_slot * BLOCK - 5
    for live in (6, S) if quick else (1, 6, 12, S):
        for context in (BLOCK - 5, span) if quick \
                else (BLOCK - 5, 2 * BLOCK - 5, span):
            lengths = np.zeros(S, np.int32)
            lengths[:live] = context
            first = 0 if window is None \
                else max(context - window + 1, 0) // BLOCK
            yield (live, context, lengths,
                   live * (-(-context // BLOCK) - first))


def kernel_us(trace_dir):
    """Device microseconds of each ``paged_decode_attention`` event of the
    newest trace under ``trace_dir``, in the order they ran."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    ops = trace_reduce.device_ops(trace_reduce.load_xplane(path))
    events = sorted((start, dur) for name, start, dur, _ in ops[0]
                    if "paged_decode_attention" in name)
    return [dur / 1e3 for _, dur in events]


def sweep(mod, form, shape, quick, emit):
    program, (q, kp, vp, tables) = layer_scan(mod, shape), inputs(shape)
    points = list(loads(shape, quick))
    jax.block_until_ready(program(q, kp, vp, tables,
                                  jnp.asarray(points[0][2])))
    sums = []
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _, _, lengths, _ in points:
                for _ in range(REPEATS):
                    out = jax.block_until_ready(
                        program(q, kp, vp, tables, jnp.asarray(lengths)))
                sums.append(np.asarray(out))
        us = kernel_us(trace_dir)
    per_point = CALLS * REPEATS
    if len(us) != per_point * len(points):
        raise RuntimeError(f"{len(us)} kernel events in the trace, "
                           f"{per_point * len(points)} calls made")
    rows = []
    for i, (live, context, _, blocks) in enumerate(points):
        mine = us[i * per_point:(i + 1) * per_point]
        rows.append((blocks, float(np.median(mine))))
        emit(form=form, shape=shape, live_slots=live, context=context,
             live_blocks=blocks, us_a_call=round(rows[-1][1], 2),
             us_a_call_max=round(max(mine), 2))
    x, y = np.array(rows).T
    per_block, fixed = np.polyfit(x, y, 1)
    emit(form=form, shape=shape, fit="us_a_call = fixed + per_block * "
         "live_blocks", fixed_us=round(float(fixed), 2),
         per_block_us=round(float(per_block), 3))
    return sums


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--parent", help="a checkout whose kernel is timed too")
    ap.add_argument("--quick", action="store_true",
                    help="four loads a shape, gpt2-large's shape alone")
    args = ap.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(f"the sweep times the chip; this is {device}")
    forms = [("change", fa)]
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_flash_attention", os.path.join(
                args.parent, "apex_tpu", "ops", "flash_attention.py"))
        parent = importlib.util.module_from_spec(spec)
        # dataclasses and NamedTuples look their module up by name
        sys.modules[spec.name] = parent
        spec.loader.exec_module(parent)
        forms.insert(0, ("parent", parent))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "paged_decode_sweep.jsonl"),
              "w") as out:
        def emit(**row):
            line = json.dumps(dict(row, device=device.device_kind))
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()
        for shape in list(SHAPES)[:1] if args.quick else SHAPES:
            sums = [sweep(mod, form, shape, args.quick, emit)
                    for form, mod in forms]
            if args.parent:
                # the arithmetic is the parent's: the same bits, load by load
                emit(shape=shape, loads_equal_to_parent=[
                    bool(np.array_equal(a, b)) for a, b in zip(*sums)])


if __name__ == "__main__":
    main()

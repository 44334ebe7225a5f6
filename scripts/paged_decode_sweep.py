"""Time the paged decode kernel alone on the chip, load by load.

    chiprun -- python scripts/paged_decode_sweep.py [--parent DIR] [--quick]

One process, one chip. At the three serving configurations' shapes
(gpt2-large: 32 slots x 20 heads x 64 over 8 blocks a slot; command-a's
full and window kinds: 32 x 16 heads on 1 KV head of 128 over 68 blocks,
window 4,096; Nemotron's attention layer: 64 x 8 on 1 x 128 over 32) it
runs ``paged_decode_attention`` as a decode layer calls it, the current
token's K and V merged in, 36 calls a program with the walk built once
outside, for 1, 6, 12 and all slots live at contexts of 1, 2 and the full
span of blocks. From a profiler trace it reads the kernel's own device
time (the events named ``paged_decode_attention``) and the whole call's
(every leaf op of the program, the XLA ops round the kernel included, over
the calls). A least-squares line through a shape's loads gives the cost a
call whatever the load and the cost a live block, for the kernel and for
the whole call. ``--parent DIR`` times another checkout's
``apex_tpu/ops/flash_attention.py`` first, for the before/after table of
``PERF.md``, and gives load by load the largest difference of the two
forms' summed outputs. JSON
lines on stdout, the table in ``chiprun_out/paged_decode_sweep.jsonl``.
"""
import argparse
import glob
import importlib
import importlib.util
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
    pool = (2, S * held + 1, BLOCK, hkv * d)
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    mk = lambda k, s: (0.5 * jax.random.normal(k, s)).astype(jnp.bfloat16)
    tables = 1 + np.arange(S)[:, None] * held + np.arange(per_slot) % held
    return (mk(keys[0], (S, h, d)), mk(keys[1], pool), mk(keys[2], pool),
            jnp.asarray(tables, jnp.int32), mk(keys[3], (S, hkv, d)),
            mk(keys[4], (S, hkv, d)))


def layer_scan(mod, shape):
    """The attention as a decode step runs it: ``CALLS`` calls, the layer
    index the scan's, the walk made once outside."""
    _, _, _, d, per_slot, window, _ = SHAPES[shape]

    def program(q, kp, vp, tables, lengths, k_new, v_new):
        work = mod.paged_work_list(lengths, BLOCK, per_slot, window)

        def one(acc, layer):
            out = mod.paged_decode_attention(
                q, kp, vp, layer % kp.shape[0], tables, lengths,
                k_new=k_new, v_new=v_new, window=window, work=work)
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


def device_us(trace_dir):
    """Of the newest trace under ``trace_dir``: device microseconds of each
    ``paged_decode_attention`` event, in the order they ran, and the
    summed microseconds of every leaf op."""
    path = max(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    ops = trace_reduce.device_ops(trace_reduce.load_xplane(path))[0]
    events = sorted((start, dur) for name, start, dur, _ in ops
                    if "paged_decode_attention" in name)
    return ([dur / 1e3 for _, dur in events],
            sum(dur for _, _, dur, _ in trace_reduce.leaves(ops)) / 1e3)


def fit(rows):
    x, y = np.array(rows).T
    per_block, fixed = np.polyfit(x, y, 1)
    return round(float(fixed), 2), round(float(per_block), 3)


def sweep(mod, form, shape, quick, emit):
    program = layer_scan(mod, shape)
    q, kp, vp, tables, k_new, v_new = inputs(shape)
    points = list(loads(shape, quick))
    run = lambda lengths: jax.block_until_ready(program(
        q, kp, vp, tables, jnp.asarray(lengths), k_new, v_new))
    run(points[0][2])
    sums, kernel, whole = [], [], []
    for _, _, lengths, _ in points:
        # one trace a load: the whole call is every op the trace holds
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                for _ in range(REPEATS):
                    out = run(lengths)
            us, total = device_us(trace_dir)
        sums.append(np.asarray(out))
        if len(us) != CALLS * REPEATS:
            raise RuntimeError(f"{len(us)} kernel events in the trace, "
                               f"{CALLS * REPEATS} calls made")
        kernel.append(float(np.median(us)))
        whole.append(total / (CALLS * REPEATS))
    for i, (live, context, _, blocks) in enumerate(points):
        emit(form=form, shape=shape, live_slots=live, context=context,
             live_blocks=blocks, us_a_call=round(kernel[i], 2),
             whole_us_a_call=round(whole[i], 2))
    for name, ys in (("kernel", kernel), ("whole", whole)):
        fixed, per_block = fit([(p[3], y) for p, y in zip(points, ys)])
        emit(form=form, shape=shape, of=name,
             fit="us_a_call = fixed + per_block * live_blocks",
             fixed_us=fixed, per_block_us=per_block)
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
                emit(shape=shape, max_abs_diff_to_parent=[
                    float(np.abs(a - b).max()) for a, b in zip(*sums)])


if __name__ == "__main__":
    main()

"""Training telemetry end to end — the worked example for
``docs/OBSERVABILITY.md``.

A pipe x data mesh runs an amp + DDP + pipelined-1F1B + fused-optimizer
toy step with a telemetry collector reaping the in-graph metrics, a
StepReporter streaming JSONL + a Chrome trace, and the runtime compile
listeners counting (re)compiles; then the numerics health watchdog
(``HealthConfig(level="cheap")``) catches an injected inf gradient,
names the offending leaf, and writes a structured crash dump — every
layer of the subsystem in ~150 lines:

    python examples/telemetry.py --steps 5
"""

import argparse
import json
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import observability as obs
from apex_tpu.amp.scaler import DynamicLossScale, all_finite
from apex_tpu.observability import health, ingraph
from apex_tpu.optimizers import FusedSGD
from apex_tpu.optimizers.fused_sgd import SGDState
from apex_tpu.parallel.distributed import allreduce_grads
from apex_tpu.transformer.pipeline_parallel.schedules import (
    forward_backward_pipelining_without_interleaving)
from jax import shard_map
from apex_tpu.utils.timers import Timers


def demo_health_watchdog(out_dir, inject_at=3, steps=5):
    """The numerics watchdog end to end: a cheap-level policy watches the
    amp grad check; at step ``inject_at`` the loss gains a term whose
    gradient overflows fp32 in exactly one leaf (``['bad']``), the
    watchdog attributes it by path, and the reporter's health hook writes
    a structured CrashDump (``on_nonfinite="dump"``)."""
    hcfg = health.HealthConfig(level="cheap", on_nonfinite="dump",
                               dump_dir=out_dir)
    scaler = DynamicLossScale(init_scale=2.0)
    params = {"w": jnp.ones((4,)), "bad": jnp.ones((2,))}
    x = jnp.arange(4.0)
    big = jnp.float32(3e38)  # d/d_bad = big * big -> inf in fp32

    def loss_fn(p, poison):
        clean = jnp.sum(p["w"] * x) ** 2
        # select between inf and 0 (a plain `* poison` would backprop
        # inf * 0 = NaN into the clean steps too)
        inject = jnp.where(poison > 0, big * big, jnp.float32(0.0))
        return clean + jnp.sum(p["bad"]) * inject

    def step(params, ls, poison):
        # activate at TRACE time: the watchdog's gates are trace-time
        # checks, exactly like ingraph.record's collector stack
        with health.activate(hcfg):
            def body(params, ls, poison):
                grads = jax.grad(loss_fn)(params, poison)
                finite = all_finite(grads)   # health/grads/* + attribution
                return scaler.update(ls, finite)
            return ingraph.reap(body)(params, ls, poison)

    jsonl_path = os.path.join(out_dir, "health.jsonl")
    hook = hcfg.reporter_hook()
    ls = scaler.init()
    jit_step = jax.jit(step)  # one wrapper: compile once, reuse each step
    with obs.StepReporter([obs.JSONLSink(jsonl_path)],
                          registry=obs.MetricsRegistry(),
                          hooks=[hook]) as reporter:
        for i in range(steps):
            poison = jnp.float32(1.0 if i == inject_at else 0.0)
            ls, metrics = jit_step(params, ls, poison)
            payload = reporter.report(i, metrics=metrics)
            blame = health.decode_attribution(payload)
            print(f"health step {i}: nonfinite "
                  f"{payload['health/grads/nonfinite_count']:.0f} "
                  f"scale {payload['amp/loss_scale']:.0f}"
                  + (f"  first bad leaf: {blame['grads']}" if blame else ""))
    assert hook.dumps, "the injected inf must have produced a dump"
    dump = json.load(open(hook.dumps[0]))
    print(f"crash dump -> {hook.dumps[0]}")
    print(f"  attribution: {dump['attribution']} "
          f"(jax {dump['versions']['jax']})")
    return hook.dumps[0]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--out-dir", default=None,
                    help="where telemetry.jsonl / host_trace.json land "
                         "(default: a temp dir, paths printed)")
    args = ap.parse_args(argv)
    out_dir = args.out_dir or tempfile.mkdtemp(prefix="apex_tpu_telemetry_")
    jsonl_path = os.path.join(out_dir, "telemetry.jsonl")
    trace_path = os.path.join(out_dir, "host_trace.json")

    # runtime layer: compile counters into the default host registry —
    # a climbing jax/compiles after step 0 would flag a recompile storm
    obs.install_compile_listeners()

    # adapt to whatever mesh the host offers (pp=dp=1 degenerates fine)
    pp = 2 if jax.device_count() >= 2 else 1
    dp = max(1, min(2, jax.device_count() // pp))
    mesh = Mesh(np.array(jax.devices()[:pp * dp]).reshape(pp, dp),
                ("pipe", "data"))
    M, mb, D = 4, 2, 16
    rng = np.random.RandomState(0)
    ws = jnp.asarray(rng.randn(pp, D, D) * 0.3, jnp.float32)
    scaler = DynamicLossScale(init_scale=2.0 ** 8)
    opt = FusedSGD(lr=1e-2, momentum=0.9)
    opt_state, ls = opt.init(ws), scaler.init()

    def stage(p, x, s):
        return jnp.tanh(x @ p["w"])

    def body(ws, opt_state, ls, micro):
        loss, grads = forward_backward_pipelining_without_interleaving(
            stage, micro, {"w": ws[0]},
            loss_fn=lambda y, m: jnp.mean(y ** 2),
            grad_scale=ls.loss_scale)
        grads = allreduce_grads(grads["w"][None], "data")  # ddp/* metrics
        finite = all_finite(grads, axis_names=("pipe",))
        new_ls = scaler.update(ls, finite)                 # amp/* metrics
        new_w, new_s = opt.step(grads, opt_state, ws,      # optim/* metrics
                                grads_finite=finite)
        return jax.lax.pmean(loss, "data"), new_w, new_s, new_ls

    def inner(*a):
        out, metrics = ingraph.reap(body)(*a)
        return out + (ingraph.aggregate(metrics, ("pipe", "data")),)

    ospec = SGDState(step=P(), momentum_buf=P("pipe"))
    step = jax.jit(lambda w, s, l, m: shard_map(
        inner, mesh=mesh,
        in_specs=(P("pipe"), ospec, P(), P(None, "data")),
        out_specs=(P(), P("pipe"), ospec, P(), P()))(w, s, l, m))

    timers = Timers()
    last = None
    with obs.StepReporter(
            [obs.JSONLSink(jsonl_path), obs.ChromeTraceSink(trace_path)],
            timers=timers, capture_spans=True) as reporter:
        for i in range(args.steps):
            micro = jnp.asarray(
                rng.randn(M, dp * mb, D), jnp.float32)
            timers("step").start()
            loss, ws, opt_state, ls, metrics = step(ws, opt_state, ls,
                                                    micro)
            timers("step").stop(wait_for=ws)
            obs.sample_memory_stats()  # HBM gauges (no-op on CPU)
            last = reporter.report(i, metrics=metrics,
                                   extra={"loss": float(loss)})
            print(f"step {i}: loss {last['loss']:.5f} "
                  f"scale {last['amp/loss_scale']:.0f} "
                  f"grad_norm {last['optim/grad_norm']:.4f} "
                  f"bubble {last['pipeline/bubble_fraction']:.3f} "
                  f"allreduce {last['ddp/allreduce_bytes']:.0f}B "
                  f"compiles {last.get('jax/compiles', 0):.0f}")

    with open(jsonl_path) as f:
        n_lines = sum(1 for _ in f)
    print(f"wrote {n_lines} JSONL events -> {jsonl_path}")
    print(f"host spans + counter tracks -> {trace_path} "
          f"(load in chrome://tracing or ui.perfetto.dev)")
    assert json.load(open(trace_path))["traceEvents"]

    demo_health_watchdog(out_dir)
    return last


if __name__ == "__main__":
    main()

"""ResNet-50 mixed-precision training — ``reference:examples/imagenet/
main_amp.py`` rebuilt on apex_tpu.

Demonstrates the O0-O3 policy surface, dynamic loss scaling with on-device
overflow skip, the FlatOptimizer tier, data-parallel training over every
local device (the DDP role), per-step timers, and checkpoint/resume.
Synthetic data by default (the reference's ``--prof`` path); swap
``synthetic_batches`` for a real input pipeline.

Run (any backend; uses all visible devices as the data axis)::

    python examples/imagenet_amp.py --opt-level O2 --steps 20
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.amp import all_finite, get_policy, make_loss_scale
from apex_tpu.checkpoint import restore_checkpoint, save_checkpoint
from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                             TrainConfig)
from apex_tpu.parallel import allreduce_grads
from apex_tpu.utils.timers import Timers
from apex_tpu.utils.vma import cast_to_vma


def synthetic_batches(rng, n, per_device_batch, devices, img=64, classes=100):
    b = per_device_batch * devices
    for _ in range(n):
        yield (rng.randn(b, img, img, 3).astype(np.float32),
               rng.randint(0, classes, b))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--opt-level", default="O2",
                    choices=["O0", "O1", "O2", "O3"])
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--per-device-batch", type=int, default=4)
    ap.add_argument("--img", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--ckpt-dir", default=None,
                    help="save/resume a checkpoint here")
    args = ap.parse_args(argv)

    n_dev = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    cfg = TrainConfig(
        model=ModelConfig(name="resnet50", num_classes=100),
        batch=BatchConfig(global_batch_size=args.per_device_batch * n_dev,
                          micro_batch_size=args.per_device_batch),
        optimizer=OptimizerConfig(name="sgd", lr=args.lr, momentum=0.9,
                                  weight_decay=1e-4, flat=True),
        opt_level=args.opt_level)
    policy = cfg.build_policy()
    model = cfg.build_model()
    opt = cfg.build_optimizer()
    scaler = cfg.build_scaler()

    params, bn_state = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    ls = scaler.init()
    start_step = 0
    if args.ckpt_dir:
        try:
            state, host = restore_checkpoint(
                args.ckpt_dir,
                {"params": params, "bn": bn_state, "opt": opt_state,
                 "ls": ls})
            params, bn_state = state["params"], state["bn"]
            opt_state, ls = state["opt"], state["ls"]
            start_step = host["step"]
            print(f"resumed from step {start_step}")
        except FileNotFoundError:
            pass

    def loss_fn(params, bn_state, x, labels, scale):
        logits, new_bn = model(params, bn_state,
                               x.astype(policy.compute_dtype), training=True)
        onehot = jax.nn.one_hot(labels, cfg.model.num_classes)
        loss = -jnp.mean(jnp.sum(
            jax.nn.log_softmax(logits.astype(jnp.float32)) * onehot, -1))
        return loss * scale, (loss, new_bn)

    @jax.jit
    def train_step(params, bn_state, opt_state, ls, x, labels):
        def inner(params, bn_state, opt_state, ls, x, labels):
            # DDP pattern: differentiate per-replica, allreduce explicitly
            varying = jax.tree_util.tree_map(
                lambda p: cast_to_vma(p, frozenset({"data"})), params)
            grads, (loss, new_bn) = jax.grad(loss_fn, has_aux=True)(
                varying, bn_state, x, labels, ls.loss_scale)
            grads = allreduce_grads(grads, "data")
            grads = scaler.unscale(ls, grads)
            finite = all_finite(grads)
            new_ls = scaler.update(ls, finite)
            params, opt_state = opt.step(grads, opt_state, params,
                                         grads_finite=finite)
            new_bn = jax.tree_util.tree_map(
                lambda s: jax.lax.pmean(s, "data") if s.dtype != jnp.int32
                else s, new_bn)
            return params, new_bn, opt_state, new_ls, \
                jax.lax.pmean(loss, "data")

        return shard_map(
            inner, mesh=mesh,
            in_specs=(P(), P(), P(), P(), P("data"), P("data")),
            out_specs=(P(), P(), P(), P(), P()))(
                params, bn_state, opt_state, ls, x, labels)

    timers = Timers()
    rng = np.random.RandomState(0)
    for step, (x, labels) in enumerate(
            synthetic_batches(rng, args.steps, args.per_device_batch,
                              n_dev, args.img, cfg.model.num_classes),
            start=start_step):
        timers("step").start()
        params, bn_state, opt_state, ls, loss = train_step(
            params, bn_state, opt_state, ls, jnp.asarray(x),
            jnp.asarray(labels))
        timers("step").stop(wait_for=loss)
        print(f"step {step}: loss {float(loss):.4f} "
              f"scale {float(ls.loss_scale):.0f}")
    timers.log(["step"], normalizer=max(args.steps, 1))

    if args.ckpt_dir:
        save_checkpoint(args.ckpt_dir,
                        {"params": params, "bn": bn_state,
                         "opt": opt_state, "ls": ls},
                        step=start_step + args.steps,
                        host_state={"step": start_step + args.steps})
        print(f"checkpointed at step {start_step + args.steps}")
    return float(loss)


if __name__ == "__main__":
    main()

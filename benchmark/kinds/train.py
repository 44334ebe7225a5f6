"""Kind ``train``: drives the hybrid trainer's
``jit_train_step(donate=True)``.

The model's sizes, the trainer, the weights' layout and the reference are
the configuration's family's (``ctx.family``, ``benchmark/families/``).
Set-up builds ONE object — the AOT-compiled donated step with its state,
the weights drawn by the family's reference from the seed — takes its
first steps through the window's own call and feed, and hands the same
object to the window. After the window has closed, the peak has been read
and the state is freed, the plain reference follows the first
``CHECK_STEPS`` steps from the same seed and the readings are compared
(``compare``).
"""

import collections
import statistics
import time

import numpy as np

from benchmark import traffic

REQUIRED_LIMITS = ("grad_gap", "grad_diff_median", "change_gap")
CHECK_STEPS = 3          # steps the reference follows
WARM_STEPS = 1           # further steps before the window opens
TRACE_SECONDS = 3.0      # the traced part of a --trace 1 window


class Job:
    """The compiled step, its state and its feed."""

    def __init__(self, ctx):
        import jax
        from jax.sharding import NamedSharding

        cfg, job, family = ctx.config, ctx.cell["job"], ctx.family
        self.ctx, self.cfg, self.job, self.family = ctx, cfg, job, family
        self.vocab = family.vocab(cfg)
        trainer, self.mesh = family.trainer(cfg, job, ctx.devices)
        self.trainer = trainer
        self.lo, self.hi = family.seed_key(ctx.seed)

        def fresh(lo, hi):
            return family.to_trainer(family.make_weights(cfg, lo, hi))

        shapes = jax.eval_shape(fresh, self.lo, self.hi)
        specs = (trainer.stage_specs(shapes[0]), trainer.shared_specs)
        shard = jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        stage_stack, shared = jax.jit(fresh, out_shardings=shard)(
            self.lo, self.hi)
        if trainer.is_zero:
            opt_state = jax.jit(jax.shard_map(
                lambda s, sh: trainer.opt.init((s, sh)), mesh=self.mesh,
                in_specs=specs,
                out_specs=trainer._zero_state_spec()))(stage_stack, shared)
            trainer.opt.check_state(opt_state)
        else:
            opt_state = jax.jit(trainer.opt.init)((stage_stack, shared))
        self.state = (stage_stack, shared, opt_state, trainer.scaler.init())
        ctx.mark("weights_and_optimizer_state")
        self.step_index = 0
        tokens, targets = self.batch(0)
        self.compiled = trainer.jit_train_step(donate=True).lower(
            *self.state, tokens, targets).compile()
        self.tokens_per_step = int(np.prod(tokens.shape))
        self.losses = []

    def batch(self, step):
        return traffic.train_batch(self.job, self.vocab, self.ctx.seed, step)

    def step(self, batch):
        """One dispatch of the timed entry: consumes the state, keeps the
        loss on the device."""
        loss, *state = self.compiled(*self.state, *batch)
        self.state = tuple(state)
        self.step_index += 1
        self.losses.append(loss)
        return loss

    # -- readings of the program's own state ---------------------------------

    def first_moment(self):
        """After step 1: Adam's first moment, leaf by leaf to the host.
        Nothing is allocated on the device, so the check leaves the
        device's peak alone; ``first_gradient`` reads it once the state is
        freed."""
        import jax
        return jax.tree_util.tree_map(np.asarray, self._moments())

    def _moments(self):
        opt_state = self.state[2]
        if not self.trainer.is_zero:
            return opt_state.exp_avg
        # ZeRO keeps one flat float32 vector, sharded over the data axis:
        # the leaves of (stage_stack, shared) raveled in tree order, then
        # padding (no bucket grid in these cells)
        import jax
        leaves, treedef = jax.tree_util.tree_flatten(
            (self.state[0], self.state[1]))
        flat, out, at = opt_state.exp_avg, [], 0
        for leaf in leaves:
            out.append(flat[at:at + leaf.size].reshape(leaf.shape))
            at += leaf.size
        return jax.tree_util.tree_unflatten(treedef, out)

    def change_norms(self):
        """The norm of each leaf's change since the seed's weights."""
        import jax
        import jax.numpy as jnp
        cfg, family = self.cfg, self.family

        def norms(stage_stack, shared, lo, hi):
            p0 = family.make_weights(cfg, lo, hi)
            p = family.from_trainer(stage_stack, shared)
            return {k: jnp.sqrt(jnp.sum(jnp.square(p[k] - p0[k])))
                    for k in p0}

        out = jax.jit(norms)(self.state[0], self.state[1], self.lo, self.hi)
        return {k: float(v) for k, v in out.items()}

    def free(self):
        import jax
        from apex_tpu.transformer import parallel_state
        for leaf in jax.tree_util.tree_leaves(self.state):
            leaf.delete()
        self.state = self.compiled = None
        parallel_state.destroy_model_parallel()


def first_gradient(ctx, moment):
    """The first gradient as Adam got it, from its first moment after step
    1 (``m = (1 - b1)(g + wd p0)``): one norm per leaf, and the gradient
    itself as host arrays. Run once the job's state is freed."""
    import jax
    import jax.numpy as jnp
    o, cfg, family = ctx.cell["job"]["optimizer"], ctx.config, ctx.family
    b1, wd = o["betas"][0], o["weight_decay"]

    def grads(moment, lo, hi):
        p0 = family.make_weights(cfg, lo, hi)
        m = family.from_trainer(*moment)
        g = {k: m[k] / (1.0 - b1) - wd * p0[k] for k in p0}
        return g, {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in g.items()}

    g, norms = jax.jit(grads)(moment, *family.seed_key(ctx.seed))
    host = {k: np.asarray(v) for k, v in g.items()}
    del g
    return {k: float(v) for k, v in norms.items()}, host


# -- the comparison ----------------------------------------------------------

def worst_leaf_gap(prog, ref, skip=()):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    median = statistics.median(ref.values())
    worst, where = 0.0, None
    for k in ref:
        if k in skip:
            continue
        gap = abs(prog[k] - ref[k]) / max(ref[k], median)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def compare(readings, ref):
    """The numbers that decide ``correct``: name -> (value, detail)."""
    out = {}
    for i, (a, b) in enumerate(zip(readings["losses"], ref["losses"]), 1):
        out[f"loss{i}_gap"] = (abs(a - b) / abs(b), f"{a:.6f} vs {b:.6f}")
    out["grad_gap"] = worst_leaf_gap(readings["grad_norms"],
                                     ref["grad_norms"])
    # the norm of the first gradient's DIFFERENCE from the reference's, leaf
    # by leaf against the reference's norm of the leaf, the median leaf's:
    # first-order in rounding, where a gap of norms is second-order, so it
    # is the number a lower precision fails
    diff = {k: d / max(ref["grad_norms"][k], 1e-30)
            for k, d in ref["grad_diff_norms"].items()}
    out["grad_diff_median"] = (statistics.median(diff.values()),
                               f"worst leaf {max(diff.values()):.4g}")
    # leaves whose reference gradient is nought to rounding move under
    # Adam by round-off alone: out of the change by a rule on the
    # reference's gradient, not by name
    median = statistics.median(ref["grad_norms"].values())
    still = [k for k, g in ref["grad_norms"].items() if g < 1e-3 * median]
    out["change_gap"] = worst_leaf_gap(readings["change_norms"],
                                       ref["change_norms"], skip=still)
    return out


def follow_reference(ctx, quant=False, keep_share=1.0, against=None,
                     keep_first_grad=False):
    """The reference's (or, with ``quant``, the control's) readings over
    the first ``CHECK_STEPS`` steps of this seed."""
    import jax
    cfg, job, family = ctx.config, ctx.cell["job"], ctx.family
    ref = family.train_reference(
        cfg, job, quant=quant, keep_share=keep_share,
        rows_per_block=job["micro_batch"] * job["dp"], devices=ctx.devices)
    lo, hi = family.seed_key(ctx.seed)
    make = jax.jit(lambda lo, hi: family.make_weights(cfg, lo, hi),
                   out_shardings=ref.tree_sh)
    batches = []
    for step in range(CHECK_STEPS):
        tokens, targets = traffic.train_batch(job, family.vocab(cfg),
                                              ctx.seed, step)
        batches.append((tokens.reshape(-1, tokens.shape[-1]),
                        targets.reshape(-1, targets.shape[-1])))
    return ref.follow(lambda: make(lo, hi), batches, against=against,
                      keep_first_grad=keep_first_grad)


def first_steps(job_obj):
    """The first ``CHECK_STEPS`` steps through the window's own call and
    feed; the program's readings for the comparison (``first_moment`` is
    turned into the first gradient by ``read_gradient`` once the state is
    freed)."""
    import jax
    readings = {}
    for step in range(CHECK_STEPS):
        jax.block_until_ready(job_obj.step(job_obj.batch(step)))
        if step == 0:
            readings["first_moment"] = job_obj.first_moment()
    readings["change_norms"] = job_obj.change_norms()
    readings["losses"] = [float(x) for x in job_obj.losses[:CHECK_STEPS]]
    return readings


def read_gradient(ctx, readings):
    """``readings`` with the first moment turned into the first gradient's
    norms; returns the gradient itself for the reference to be held
    against."""
    readings["grad_norms"], first_grad = first_gradient(
        ctx, readings.pop("first_moment"))
    return first_grad


def step_memory(compiled):
    """What the compiler says the step holds, in bytes (``None`` where the
    backend does not say)."""
    m = compiled.memory_analysis()
    if m is None:
        return None
    return {k: int(getattr(m, k + "_size_in_bytes"))
            for k in ("argument", "output", "alias", "temp",
                      "generated_code")}


def run(ctx):
    import jax

    job = Job(ctx)
    ctx.mark("state_and_compiled_step")
    ctx.say(kind="train", tokens_per_step=job.tokens_per_step,
            kernel_in_step="tpu_custom_call" in job.compiled.as_text(),
            step_memory_analysis=step_memory(job.compiled))
    readings = first_steps(job)
    ctx.mark("first_steps_and_readings")
    for _ in range(WARM_STEPS):
        jax.block_until_ready(job.step(job.batch(job.step_index)))

    # -- the window ---------------------------------------------------------
    in_flight = collections.deque()
    batch = job.batch(job.step_index)
    first_step = job.step_index
    traced = None
    with ctx.no_compiles() as compiles:
        ctx.open_window()
        t0 = time.perf_counter()
        if ctx.trace:
            ctx.start_trace()
            ta = time.perf_counter()
        while True:
            with ctx.span("train_step"):
                loss = job.step(batch)
            in_flight.append(loss)
            with ctx.span("make_batch"):
                batch = job.batch(job.step_index)
            if len(in_flight) > 1:
                with ctx.span("wait_step"):
                    in_flight.popleft().block_until_ready()
            now = time.perf_counter()
            if ctx.trace and traced is None and now - ta >= TRACE_SECONDS:
                jax.block_until_ready((loss, job.state))
                tb = time.perf_counter()
                traced = dict(seconds=tb - ta,
                              steps=job.step_index - first_step)
                ctx.stop_trace()
                in_flight.clear()
                now = time.perf_counter()
            if now - t0 >= ctx.seconds:
                break
        jax.block_until_ready((loss, job.state))
        t1 = time.perf_counter()
    steps = job.step_index - first_step
    window_s = t1 - t0
    losses = [float(x) for x in job.losses]
    peak = ctx.memory_peak_bytes()
    ctx.say(window_s=window_s, steps_in_window=steps,
            compilations_in_window=compiles.count,
            loss_first=losses[0], loss_last=losses[-1],
            loss_window_first=losses[first_step])
    failed = sum(1 for x in losses[first_step:] if not np.isfinite(x))
    facts = dict(tokens_per_step=job.tokens_per_step, traced=traced,
                 kind="train")
    job.free()
    del job

    # -- the reference, once the window has closed and the state is freed ---
    t_ref = time.perf_counter()
    ref = follow_reference(ctx, against=read_gradient(ctx, readings))
    ctx.say(reference_s=time.perf_counter() - t_ref,
            reference_losses=ref["losses"])
    checks = compare(readings, ref)
    return dict(
        attempted=steps, failed=failed,
        end_to_end={"train_tokens_per_s":
                    steps * facts["tokens_per_step"] / window_s},
        checks=checks, compilations=compiles.count, facts=facts,
        memory_peak_bytes=peak)

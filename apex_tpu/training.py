"""High-level train-step builders.

The reference leaves loop assembly to users (NeMo/Megatron-style trainers);
here the one genuinely intricate assembly — the hybrid TP x PP x DP GPT
step with pipelined embedding + tied head — is packaged once and shared by
``examples/gpt_pretrain.py`` and the driver dryrun (``__graft_entry__``),
so the spec plumbing lives in exactly one place.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from apex_tpu.config import TrainConfig
from apex_tpu.observability import health as _health
from apex_tpu.observability import ingraph
from apex_tpu.optimizers import AdamState
from apex_tpu.optimizers.distributed_fused import (_DistributedFusedBase,
                                                   ZeroAdamState)
from apex_tpu.parallel.distributed import allreduce_grads
from apex_tpu.transformer.amp import GradScaler
from apex_tpu.transformer.pipeline_parallel import (
    forward_backward_pipelining_without_interleaving)
from apex_tpu.utils.vma import cast_to_vma, scan_stable_vma

__all__ = ["GPTHybridTrainer", "accumulate_gradients",
           "resolve_bucket_bytes"]


def resolve_bucket_bytes(cfg: TrainConfig, model, mesh) -> int:
    """Resolve ``ddp_bucket_bytes="auto"`` for one trainer: price the
    model's per-microbatch fwd+bwd with the pyprof roofline and hand
    :func:`apex_tpu.pyprof.tune_bucket_bytes` the resulting hide window
    (smallest bucket whose RS+AG wire time is fully hideable — see
    pyprof/tune.py for the decision rule).

    Pricing convention: a single-chip twin of the model (tp=1, SP off —
    the sharded program would need a bound mesh just to trace) is traced
    abstractly at the config's ``(micro_batch, seq)`` shape, its modeled
    non-comm time divided by ``tp*pp`` (each chip computes ~1/(tp*pp) of
    the model) and multiplied by the M microbatches whose backwards all
    run inside one sync window. Estimates feed a bucket-size *choice*,
    not a perf claim — candidates are powers of two, so only the order
    of magnitude matters. Deterministic for a given config + device
    spec (the resolved grid is a checkpoint-layout property: the ZeRO
    ``bucket_stamp`` persists it). Unpriceable models fall back loudly
    to ``DEFAULT_BUCKET_BYTES`` inside ``tune_bucket_bytes``."""
    from apex_tpu.observability.registry import get_registry
    from apex_tpu.pyprof import tune_bucket_bytes
    from apex_tpu.pyprof.model import model_program

    mesh_shape = dict(mesh.shape)
    dp = int(mesh_shape.get("data", 1))
    tp = int(mesh_shape.get("tensor", 1))
    pp = int(mesh_shape.get("pipe", 1))
    mb = cfg.batch.micro_batch_size
    num_micro = max(1, cfg.batch.global_batch_size // max(1, mb * dp))
    try:
        twin = type(model)(dataclasses.replace(
            model.cfg, tensor_model_parallel_size=1,
            sequence_parallel=False, tp_comm_overlap=False))
        pshapes = jax.eval_shape(twin.init, jax.random.PRNGKey(0))
        # per-chip on BOTH sides of the decision rule: each chip syncs
        # its own 1/(tp*pp) parameter shard over the dp ring, and hides
        # it under its own 1/(tp*pp) slice of the model's compute
        grad_bytes = 4.0 * sum(
            int(np.prod(l.shape)) if l.shape else 1
            for l in jax.tree_util.tree_leaves(pshapes)) / (tp * pp)
        seq = model.cfg.max_position_embeddings
        tokens = jax.ShapeDtypeStruct((mb, seq), jnp.int32)

        def fwd_bwd(params, tokens):
            return jax.grad(lambda p: twin.loss(p, tokens, tokens))(params)

        traced = jax.jit(fwd_bwd).trace(pshapes, tokens)
        cost = model_program(traced)
        hide_ms = sum(max(r.compute_ms, r.hbm_ms)
                      for r in cost.regions.values()) \
            * num_micro / (tp * pp)
        spec = cost.spec
    except Exception as e:
        # loud with the REAL reason — a swallowed pricing error would
        # leave every "auto" run on the default grid with a warning
        # blaming missing inputs instead of the actual failure
        import warnings

        from apex_tpu.parallel.distributed import DEFAULT_BUCKET_BYTES
        warnings.warn(
            f'ddp_bucket_bytes="auto": roofline pricing of the model '
            f"failed ({e!r}); falling back to DEFAULT_BUCKET_BYTES="
            f"{DEFAULT_BUCKET_BYTES}", stacklevel=2)
        resolved = DEFAULT_BUCKET_BYTES
    else:
        resolved = tune_bucket_bytes(grad_bytes=grad_bytes, axis_size=dp,
                                     hide_ms=hide_ms, spec=spec)
    get_registry().gauge("ddp/auto_bucket_bytes").set(float(resolved))
    return int(resolved)


def accumulate_gradients(ddp, loss_fn, params, microbatches):
    """Gradient accumulation with one DDP allreduce per window — the real
    implementation of ``DistributedDataParallel(delay_allreduce=True)``
    (apex's ``distributed.py:162`` flag; torch-DDP ``no_sync`` semantics).

    ``loss_fn(params, microbatch) -> scalar``; ``microbatches`` is a pytree
    of arrays with a leading accumulation axis ``K``. Each microbatch is
    differentiated with per-replica (unsynced) grads, the K grad trees are
    summed *locally* in a scan, and :meth:`ddp.sync_gradients
    <apex_tpu.parallel.distributed.DistributedDataParallel.sync_gradients>`
    fires exactly once on the mean — so the jaxpr carries one psum per
    window instead of K (asserted by
    ``tests/test_parallel.py::test_accumulate_gradients_single_psum``),
    cutting DP traffic by K× at identical numerics (grad of the mean loss
    over the window, then DDP's numeric policy).

    Must run where ``ddp.axis_name`` is bound (validated at trace time —
    an unbound axis or an empty window, ``num_micro == 0``, raises
    ``ValueError`` instead of tracing a silently-NaN program). With a
    bucketed ``ddp`` (``DistributedDataParallel(bucket_bytes=...)``) the
    window sync fires as B flat fp32 buckets in the scan epilogue — B
    independent collectives XLA can overlap with epilogue work that does
    not consume the synced grads. Returns ``(mean_loss, synced_grads)``;
    the loss is this replica's local window mean (pmean it over the data
    axis if a replicated value is needed).
    """
    leading = {jnp.shape(l)[0]
               for l in jax.tree_util.tree_leaves(microbatches)}
    if len(leading) != 1:
        raise ValueError(
            f"microbatch leaves disagree on the accumulation axis: "
            f"{sorted(leading)}")
    num_micro = leading.pop()
    if num_micro == 0:
        # without this the scan produces all-zero grads and the 0/0 window
        # mean is a silent NaN loss — fail loudly at trace time instead
        raise ValueError(
            "accumulate_gradients got an empty accumulation window "
            "(num_micro == 0); every microbatch leaf has leading dim 0")
    try:
        jax.lax.axis_size(ddp.axis_name)
    except Exception as e:
        # axis_size raises (NameError on most jax lines) when the name is
        # unbound; surface a trace-placement error, not a deep psum failure
        raise ValueError(
            f"accumulate_gradients must be traced where ddp.axis_name="
            f"{ddp.axis_name!r} is bound (inside shard_map/pmap over that "
            f"mesh axis); it is not bound here") from e
    params_v = jax.tree_util.tree_map(
        lambda p: cast_to_vma(p, frozenset({ddp.axis_name})), params)

    def body(carry, mb):
        acc, loss_sum = carry
        loss, grads = jax.value_and_grad(loss_fn)(params_v, mb)
        acc = jax.tree_util.tree_map(jnp.add, acc, grads)
        return (acc, loss_sum + loss), None

    zeros = jax.tree_util.tree_map(
        lambda p: jnp.zeros(jnp.shape(p), jnp.result_type(p)), params_v)
    (acc, loss_sum), _ = scan_stable_vma(
        body, (zeros, jnp.zeros((), jnp.float32)), microbatches)
    mean_grads = jax.tree_util.tree_map(lambda g: g / num_micro, acc)
    return loss_sum / num_micro, ddp.sync_gradients(mean_grads)


class GPTHybridTrainer:
    """Everything needed to train the flagship GPT over a
    ``tp x pp x dp`` mesh from one :class:`~apex_tpu.config.TrainConfig`:

        trainer = GPTHybridTrainer(cfg, mesh)
        state = trainer.init_state(jax.random.PRNGKey(0))
        step = jax.jit(trainer.train_step)
        loss, *state = step(*state, tokens, targets)

    ``tokens``/``targets``: ``(M, dp*mb, seq)`` int arrays (sharded over
    ``data`` on axis 1). The step runs the pipelined schedule with the
    vocab-parallel embedding on stage 0 and the tied head + loss on the
    last stage, DP grad averaging, MP-synced dynamic loss scaling, and the
    config's optimizer over (stage, shared) params.
    """

    def __init__(self, cfg: TrainConfig, mesh, init_scale: float = 2.0 ** 8,
                 health=None):
        """``health`` is a
        :class:`~apex_tpu.observability.health.HealthConfig` (default:
        the config's ``cfg.build_health()``, itself defaulting to
        ``level="off"``). With any level above off, the numerics watchdog
        rides :meth:`train_step_with_metrics` — ``health/*`` metrics (and
        at ``level="full"`` the data-axis replica-agreement checks) land
        in the step's Metrics pytree; the uninstrumented
        :meth:`train_step` and the ``level="off"`` program stay
        jaxpr-identical to an unconfigured trainer (asserted in tests)."""
        self.mesh = mesh
        self.health = health if health is not None else cfg.build_health()
        self.pp = cfg.parallel.pipeline_model_parallel_size
        self.model = cfg.build_model()
        # DP-sync bucketing (None = per-leaf psums / monolithic ZeRO
        # collectives, provably identical to the pre-bucketing trainer).
        # "auto" resolves HERE, against this model/mesh via the pyprof
        # roofline, and the resolved int is stored back into the config —
        # to_dict()/checkpoint sidecars carry the concrete grid, and the
        # ZeRO bucket_stamp guard keys on the same value.
        bb = cfg.ddp_bucket_bytes
        if bb == "auto":
            cfg = dataclasses.replace(
                cfg, ddp_bucket_bytes=resolve_bucket_bytes(
                    cfg, self.model, mesh))
        elif not (bb is None or isinstance(bb, int)):
            raise ValueError(
                f'ddp_bucket_bytes must be None, an int, or "auto"; '
                f"got {bb!r}")
        self.cfg = cfg
        self.bucket_bytes = cfg.ddp_bucket_bytes
        # Activation-remat policy (apex_tpu/remat.py), resolved by the
        # model from ModelConfig.remat_policy / the deprecated remat bool.
        # The pipelined stage_fn is wrapped inside the model, so the
        # schedules' own remat flag stays False here; surfaced for
        # introspection and for the bench/report plumbing
        # (StepReporter.attach_memory_budget makes the policy's HBM trade
        # measurable as mem/* gauges).
        self.remat_policy = getattr(self.model, "remat_policy", None)
        self.opt = cfg.build_optimizer()
        # ZeRO (OptimizerConfig.zero): DistributedFused* shards optimizer
        # state 1/dp over the data axis — its init/step run inside the
        # mesh'd region and its grad comm is the reduce_scatter itself
        # (reference:apex/contrib/optimizers/distributed_fused_adam.py:409)
        self.is_zero = isinstance(self.opt, _DistributedFusedBase)
        self.scaler = GradScaler(init_scale=init_scale)
        _, self.split_params = self.model.stage_fn(self.pp)

    # -- state ------------------------------------------------------------
    def init_state(self, key: jax.Array) -> Tuple[Any, Any, Any, Any]:
        params = self.model.init(key)
        stage_stack = self.split_params(params)
        shared = {"embedding": params["embedding"],
                  "final_ln": params["final_ln"]}
        if self.is_zero:
            sspec = self.stage_specs(stage_stack)
            opt = self.opt

            def init_inner(stage_stack, shared):
                return opt.init((stage_stack, shared))

            opt_state = jax.jit(jax.shard_map(
                init_inner, mesh=self.mesh,
                in_specs=(sspec, self.shared_specs),
                out_specs=self._zero_state_spec()))(stage_stack, shared)
        else:
            opt_state = self.opt.init((stage_stack, shared))
        return stage_stack, shared, opt_state, self.scaler.init()

    def _zero_state_spec(self):
        # every device owns a distinct flat shard (its pipe stage x its
        # tensor slice x its 1/dp chunk): fully sharded along dim 0
        flat = P(("pipe", "data", "tensor"))
        return ZeroAdamState(step=P(), master=flat, exp_avg=flat,
                             exp_avg_sq=flat, bucket_stamp=P())

    # -- shardings --------------------------------------------------------
    @staticmethod
    def stage_specs(stage_stack) -> Any:
        # per-layer TP stacks carry (pp, per, tp, ...); ln leaves don't
        return jax.tree_util.tree_map(
            lambda p: P("pipe", None, "tensor") if p.ndim >= 4
            else P("pipe"), stage_stack)

    shared_specs = {
        "embedding": {"word": {"weight": P("tensor")}, "position": P()},
        "final_ln": {"weight": P(), "bias": P()},
    }

    def state_specs(self, stage_stack):
        specs_p = (self.stage_specs(stage_stack), self.shared_specs)
        ospec = (self._zero_state_spec() if self.is_zero else
                 AdamState(step=P(), exp_avg=specs_p, exp_avg_sq=specs_p))
        return (specs_p[0], specs_p[1], ospec, P())

    # -- the step ---------------------------------------------------------
    def train_step(self, stage_stack, shared, opt_state, ls, tokens,
                   targets):
        return self._step_impl(False, stage_stack, shared, opt_state, ls,
                               tokens, targets)

    def jit_train_step(self, with_metrics: bool = False,
                       donate: bool = True,
                       verify_donation: bool = False):
        """``jax.jit`` of :meth:`train_step` (or
        :meth:`train_step_with_metrics`) with ``stage_stack``/``shared``/
        ``opt_state`` donated (``donate_argnums=(0, 1, 2)``): the step
        consumes each and returns its successor, so donation lets XLA
        update parameters and optimizer state in place instead of holding
        both generations live — the per-step HBM high-water drops by about
        a full parameter+optimizer copy (asserted on the compiled
        ``input_output_alias`` in tests). Callers must treat the passed
        state as consumed (standard donated-jit contract); pass
        ``donate=False`` to keep the old copy valid.

        On the ZeRO path the returned callable also validates the
        optimizer state's bucket-grid stamp on its FIRST dispatch — a
        checkpoint trained under a different ``ddp_bucket_bytes`` enters
        the step exactly there, and its bucket-major shard order would
        otherwise be silently permuted (see
        :meth:`~apex_tpu.optimizers.distributed_fused.
        _DistributedFusedBase.check_state`). First-call-only on purpose:
        reading the stamp forces a host sync, and every later state is
        this step's own output with the stamp threaded through unchanged
        — a per-step check would serialize the async dispatch pipeline
        for a constant. The ``.lower`` AOT surface is the raw jit's and
        does NOT validate — AOT callers restoring checkpoints must call
        ``trainer.opt.check_state(opt_state)`` themselves.

        ``verify_donation=True`` adds the donation-annotated-entry-point
        self-check (analysis rule ``jaxpr-donation``, docs/ANALYSIS.md)
        on the first dispatch: the step is AOT-compiled (sharded
        programs pair donations with outputs at XLA compile time, not at
        lowering) and every donated leaf must appear in the compiled
        ``input_output_alias``, with no buffer passed twice across the
        donated arguments — raises ``AnalysisError`` otherwise. The
        verified executable then serves every subsequent dispatch, so
        verification costs one AOT compile total, not one extra per
        step; requires ``donate=True`` (and, like any AOT program, the
        argument shapes/shardings of the first call).
        """
        if verify_donation and not donate:
            raise ValueError("verify_donation checks the donated "
                             "program; pass donate=True")
        fn = (self.train_step_with_metrics if with_metrics
              else self.train_step)
        jitted = jax.jit(fn, donate_argnums=(0, 1, 2) if donate else ())
        if not self.is_zero and not verify_donation:
            return jitted
        opt = self.opt if self.is_zero else None
        pending = [True]
        impl = [jitted]

        def checked(stage_stack, shared, opt_state, ls, tokens, targets):
            if pending:
                if opt is not None:
                    opt.check_state(opt_state)
                if verify_donation:
                    from apex_tpu.analysis.program import (
                        check_donation, verify_findings)
                    donated = (stage_stack, shared, opt_state)
                    expected = sum(
                        len(jax.tree_util.tree_leaves(t))
                        for t in donated)
                    compiled = jitted.lower(
                        stage_stack, shared, opt_state, ls, tokens,
                        targets).compile()
                    verify_findings(check_donation(
                        compiled, donated_args=donated,
                        expected_donated=expected,
                        label="GPTHybridTrainer.jit_train_step"),
                        "GPTHybridTrainer.jit_train_step donation")
                    impl[0] = compiled
                pending.clear()
            return impl[0](stage_stack, shared, opt_state, ls, tokens,
                           targets)

        checked.lower = jitted.lower  # raw AOT surface (no stamp check)
        return checked

    def attribution_report(self, stage_stack, shared, opt_state, ls,
                           tokens, targets, *, step_time_s=None, iters=3,
                           spec=None, regions=None, trace_dir=None,
                           spans=None, trace_steps=1):
        """Per-region step-time attribution of THIS trainer's jitted step
        (:mod:`apex_tpu.pyprof`): traces the step over the given state,
        prices every ``named_scope`` region against the chip roofline
        (FLOPs / HBM bytes / ICI bytes — the ``pipe x data x tensor``
        collectives priced ring-hop-aware), measures the wall step time
        when ``step_time_s`` is not supplied (``iters`` timed executions
        of the freshly compiled step, donation off so the caller's state
        stays valid), and returns the
        :class:`~apex_tpu.pyprof._attribute.AttributionReport` — markdown
        via ``.markdown()``, JSONL via ``.json_lines()``, and the
        ``perf/*`` gauges via ``StepReporter.attach_attribution``.
        ``trace_dir``/``spans`` upgrade the exposure accounting from
        modeled-share scaling to measured per-region walls
        (``trace_steps`` = steps the capture spans, so trace walls read
        per-step)."""
        args = (stage_stack, shared, opt_state, ls, tokens, targets)
        traced = jax.jit(self.train_step).trace(*args)
        compiled = traced.lower().compile()
        if step_time_s is None:
            import time as _time
            from apex_tpu.utils.timers import device_fence
            out = compiled(*args)
            device_fence(out)
            t0 = _time.perf_counter()
            for _ in range(max(1, iters)):
                out = compiled(*args)
            device_fence(out)
            step_time_s = (_time.perf_counter() - t0) / max(1, iters)
        from apex_tpu.pyprof import attribute
        kwargs = {} if regions is None else {"regions": regions}
        return attribute(traced, step_time_s, compiled=compiled,
                         spec=spec, trace_dir=trace_dir, spans=spans,
                         trace_steps=trace_steps, **kwargs)

    def train_step_with_metrics(self, stage_stack, shared, opt_state, ls,
                                tokens, targets):
        """:meth:`train_step` plus the step's telemetry: returns
        ``(loss, stage_stack, shared, opt_state, ls, metrics)`` where
        ``metrics`` is an
        :class:`~apex_tpu.observability.ingraph.Metrics` pytree of device
        scalars (``amp/*``, ``ddp/*``, ``pipeline/*``, ``optim/*``),
        already psum/pmean-aggregated over the whole mesh — hand it to a
        :class:`~apex_tpu.observability.report.StepReporter`. Compiles a
        separate program from :meth:`train_step`; the uninstrumented step
        stays byte-identical."""
        return self._step_impl(True, stage_stack, shared, opt_state, ls,
                               tokens, targets)

    def _step_impl(self, with_metrics, stage_stack, shared, opt_state, ls,
                   tokens, targets):
        model, opt, scaler, pp = self.model, self.opt, self.scaler, self.pp

        def body(stage_stack, shared, opt_state, ls, tokens, targets):
            # full-level watchdog: params enter the step data-replicated,
            # so any divergence across the data axis is silent replica
            # corruption; trace-time-gated no-op below level="full"
            _health.observe_replica_agreement((stage_stack, shared),
                                              "data", name="params")
            # rebuild the pipeline closures over THIS dp-rank's targets
            stage, embed_fn, head_fn, _, _ = model.pipeline_fns(pp, targets)
            if getattr(model.cfg, "tp_comm_overlap", False):
                # the pipelined path runs the layer stack via stage_fn (not
                # transform()), so the tp/* ring telemetry is recorded here:
                # M microbatch passes on a (mb, s/tp, h) activation shard
                mcfg = model.cfg
                model.record_tp_overlap(
                    (tokens.shape[1],
                     tokens.shape[2] // mcfg.tensor_model_parallel_size,
                     mcfg.hidden_size),
                    passes=tokens.shape[0])
            # DDP pattern: params enter the differentiated region
            # data-VARYING so AD yields per-replica grads, averaged
            # explicitly below (the instrumented DDP allreduce)
            vary = lambda t: jax.tree_util.tree_map(
                lambda x: cast_to_vma(x, frozenset({"data"})), t)
            my_stage = vary(jax.tree_util.tree_map(
                lambda p: p[0], stage_stack))
            loss, (sg, shg) = \
                forward_backward_pipelining_without_interleaving(
                    stage, tokens, my_stage, loss_fn=head_fn,
                    shared_params=vary(shared), embed_fn=embed_fn,
                    grad_scale=ls.loss_scale)
            grads = (jax.tree_util.tree_map(lambda g: g[None], sg), shg)
            # (ZeRO: the optimizer's psum_scatter/dp IS the DDP mean —
            # reduce_scatter replaces the allreduce, the ZeRO comm win.
            # With bucket_bytes set the apply is backward-interleaved:
            # each bucket's RS ravels span-locally from only its own
            # grad leaves, so the scheduler issues it under the tail of
            # the backward/accumulation window, and each param leaf
            # unravels from only its own buckets' gathers — bucket k's
            # AG rides under bucket k+1's RS + shard math. The finite
            # check below therefore consumes the LOCAL grads, never the
            # bucket collectives: the scale/skip select is one tiny
            # flag the transfers can run under.)
            if self.is_zero:
                # grads are still per-data-rank here, so the skip decision
                # must sync over data too (the reference's distributed
                # optimizer allreduces found_inf over the world,
                # distributed_fused_adam.py:409 region)
                from apex_tpu.amp.scaler import all_finite
                finite = all_finite(
                    grads, axis_names=(*scaler.model_parallel_axes, "data"))
            elif self.bucket_bytes is not None:
                # bucketed epilogue: the finite-check consumes the LOCAL
                # grads, pmin-synced over (mp axes + data) — the
                # reference's distributed found_inf allreduce — so the
                # loss-scale update and skip select depend on one tiny
                # flag, not on the bucket psums, and XLA can run them
                # under the bucket transfers. (A finite local tree whose
                # cross-replica SUM overflows fp32 is the one case this
                # decides differently from checking the synced grads;
                # the reference accepts the same trade.)
                from apex_tpu.amp.scaler import all_finite
                finite = all_finite(
                    grads, axis_names=(*scaler.model_parallel_axes, "data"))
                grads = allreduce_grads(grads, "data",
                                        bucket_bytes=self.bucket_bytes)
            else:
                grads = allreduce_grads(grads, "data")
                finite = scaler.all_finite_synced(grads)
            new_ls = scaler.update(ls, finite)
            new_p, new_s = opt.step(grads, opt_state,
                                    (stage_stack, shared),
                                    grads_finite=finite)
            return (jax.lax.pmean(loss, "data"), new_p[0], new_p[1],
                    new_s, new_ls)

        if with_metrics:
            def inner(*args):
                # reap INSIDE shard_map: the recorded scalars live at this
                # trace level; aggregation over every mesh axis makes them
                # replicated, so a prefix P() out_spec carries them out.
                # The health policy activates around the same trace so the
                # watchdog's trace-time gates see it.
                with _health.activate(self.health):
                    out, metrics = ingraph.reap(body)(*args)
                return out + (ingraph.aggregate(
                    metrics, tuple(self.mesh.axis_names)),)
        else:
            inner = body

        sspec = self.stage_specs(stage_stack)
        _, shspec, ospec, lspec = self.state_specs(stage_stack)
        out_specs = (P(), sspec, shspec, ospec, lspec)
        if with_metrics:
            out_specs = out_specs + (P(),)
        return jax.shard_map(
            inner, mesh=self.mesh,
            in_specs=(sspec, shspec, ospec, lspec,
                      P(None, "data"), P(None, "data")),
            out_specs=out_specs)(
                stage_stack, shared, opt_state, ls, tokens, targets)

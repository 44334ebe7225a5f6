"""Unified training config tree.

The reference carries three config systems (SURVEY §5): amp's ``Properties``
policy object (``reference:apex/amp/frontend.py:7-97``), the 808-line
Megatron argparse namespace (``reference:apex/transformer/testing/
arguments.py`` + process-global ``get_args()``), and setup.py build flags.
Here they collapse into one typed dataclass tree with plain constructors —
no globals, no argparse, no feature-detect imports (every op has an XLA
path; Pallas selection is a runtime capability check).

``TrainConfig`` is the single object a trainer needs: it *builds* the
pieces (model, optimizer, policy, scaler, microbatch calculator, samplers)
rather than being threaded into them, so each subsystem keeps its explicit
functional API. ``to_dict``/``from_dict`` give a JSON-serializable form for
the checkpoint ``host_state`` sidecar.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

__all__ = ["ModelConfig", "ParallelConfig", "BatchConfig", "OptimizerConfig",
           "TrainConfig"]


def _asdict(obj) -> dict:
    return dataclasses.asdict(obj)


# optimizers with a ZeRO (DistributedFused*) variant — the single source
# for build_optimizer's zero dispatch and fastpath()'s capability check,
# so adding a variant cannot silently leave one of them stale
ZERO_CAPABLE_OPTIMIZERS = ("adam", "adamw", "lamb")


def _zero_enabled(v) -> bool:
    """Normalize ``OptimizerConfig.zero``: accepts the legacy bool plus the
    stage spelling (``"off" | 1 | "1"``) — ZeRO stage 1 (sharded optimizer
    state) is the only stage this library implements, so anything truthy
    beyond stage 1 is rejected loudly."""
    if v in (False, 0, None) or v == "off":
        return False
    if v in (True, 1) or v == "1":
        return True
    raise ValueError(
        f"unsupported zero={v!r}; expected off|1 (bools accepted)")


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Network-size args (``arguments.py`` ``_add_network_size_args``)."""
    name: str = "gpt"                 # "gpt" | "bert" | "resnet50"
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_attention_heads: int = 12
    max_position_embeddings: int = 1024
    ffn_hidden_size: Optional[int] = None
    hidden_dropout: float = 0.0
    attention_dropout: float = 0.0
    num_classes: int = 1000           # resnet head
    # Activation rematerialization (gpt/bert). ``remat_policy``:
    # None | "none" | "full" | "selective" | "offload" — the named-policy
    # knob (apex_tpu/remat.py; "selective" keeps GEMM/flash outputs
    # resident, recomputing only the cheap LN/gelu tier). ``remat: bool``
    # is the deprecated all-or-nothing spelling, honored (True -> "full",
    # with a DeprecationWarning) only while remat_policy is None.
    # ``remat_names``: custom save/offload list for the name-based modes
    # (members of remat.CHECKPOINT_NAMES).
    remat: bool = False
    remat_policy: Optional[str] = None
    remat_names: Optional[Tuple[str, ...]] = None
    # Megatron-LM sequence parallelism (gpt only; needs tp > 1, pp == 1)
    sequence_parallel: bool = False
    # ring-decomposed SP collectives overlapping their GEMMs (gpt only;
    # needs sequence_parallel — see tensor_parallel.collective_matmul)
    tp_comm_overlap: bool = False


@dataclasses.dataclass(frozen=True)
class ParallelConfig:
    """Mesh axes (``arguments.py`` ``_add_distributed_args`` /
    ``parallel_state.initialize_model_parallel``)."""
    tensor_model_parallel_size: int = 1
    pipeline_model_parallel_size: int = 1
    virtual_pipeline_model_parallel_size: Optional[int] = None
    context_parallel_size: int = 1
    # multi-host layout rule (parallel_state._dcn_device_grid): lay the
    # data axis outermost over the process (DCN) dimension, tp/pp/cp
    # strictly intra-process. None = auto (on exactly when the device
    # set spans >1 process); explicit True/False overrides.
    dcn_data_parallel: Optional[bool] = None


@dataclasses.dataclass(frozen=True)
class BatchConfig:
    """Batch sizing (``arguments.py`` ``_add_training_args`` +
    ``microbatches.py``)."""
    global_batch_size: int = 64
    micro_batch_size: int = 8
    rampup_batch_size: Optional[Tuple[int, int, int]] = None


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """Optimizer selection (``arguments.py`` ``_add_learning_rate_args``)."""
    name: str = "adam"                # adam|adamw|sgd|lamb|novograd|adagrad
    lr: float = 1e-4
    weight_decay: float = 0.01
    betas: Tuple[float, float] = (0.9, 0.999)
    eps: float = 1e-8
    momentum: float = 0.9             # sgd
    flat: bool = False                # wrap in FlatOptimizer
    # ZeRO stage over the data axis: off | 1 (bools accepted) selects
    # DistributedFusedAdam/LAMB — optimizer state sharded 1/dp, grads
    # reduce-scattered, updated params all-gathered (per-bucket when
    # TrainConfig.ddp_bucket_bytes is set)
    zero: Any = False


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig = ModelConfig()
    parallel: ParallelConfig = ParallelConfig()
    batch: BatchConfig = BatchConfig()
    optimizer: OptimizerConfig = OptimizerConfig()
    opt_level: str = "O2"             # amp policy preset
    half_dtype: str = "bfloat16"
    seed: int = 1234
    # numerics watchdog (observability.health): in-graph instrumentation
    # tier ("off" is provably zero-cost) + host reaction to a non-finite
    # step ("skip" keeps amp's silent select-skip; "dump"/"raise" write a
    # structured CrashDump via the StepReporter health hook).
    # health_consecutive: fire raise/dump only after N non-finite reports
    # in a row — fp16 + dynamic loss scaling should set >= 2, because the
    # scaler's growth calibration overflows by design (see HealthConfig)
    health_level: str = "off"         # off | cheap | full
    health_on_nonfinite: str = "skip"  # raise | dump | skip
    health_consecutive: int = 1
    health_dump_dir: str = "."
    # DP gradient-sync bucketing (parallel/distributed.py bucketing
    # engine): bytes per flat fp32 bucket for the DDP allreduce and the
    # ZeRO reduce-scatter/all-gather. None = disabled — the trainer step
    # is provably identical to the pre-bucketing program (asserted on the
    # jaxpr, the same contract as health level="off"). "auto" = resolve
    # via the pyprof roofline (pyprof.tune_bucket_bytes: smallest bucket
    # whose RS+AG wire time hides under the modeled backward compute);
    # GPTHybridTrainer resolves it at construction and stores the
    # resolved int back into its config, so checkpoints/sidecars always
    # carry the concrete grid (the ZeRO bucket_stamp layout contract).
    ddp_bucket_bytes: Any = None

    # -- serialization ----------------------------------------------------
    def to_dict(self) -> dict:
        return _asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TrainConfig":
        d = dict(d)
        for field, sub in (("model", ModelConfig),
                           ("parallel", ParallelConfig),
                           ("batch", BatchConfig),
                           ("optimizer", OptimizerConfig)):
            if field in d and isinstance(d[field], dict):
                sub_d = dict(d[field])
                if field == "optimizer" and "betas" in sub_d:
                    sub_d["betas"] = tuple(sub_d["betas"])
                if field == "batch" and sub_d.get("rampup_batch_size"):
                    sub_d["rampup_batch_size"] = tuple(
                        sub_d["rampup_batch_size"])
                if field == "model" and sub_d.get("remat_names"):
                    sub_d["remat_names"] = tuple(sub_d["remat_names"])
                d[field] = sub(**sub_d)
        return cls(**d)

    # -- presets ----------------------------------------------------------
    _KEEP = object()   # fastpath() sentinel: no explicit bucket override

    def fastpath(self, *, bucket_bytes: Any = _KEEP) -> "TrainConfig":
        """The flagship compound-overlap preset, one declarative config:
        everything the overlap machinery can hide, turned on together —

        - ``zero=1`` — ZeRO-1 sharded optimizer with per-bucket
          backward-interleaved RS→math→AG chains
          (:mod:`apex_tpu.optimizers.distributed_fused`);
        - ``ddp_bucket_bytes`` — the bucket grid those chains pipeline
          over; a grid already set on the receiver is KEPT (it is a
          checkpoint-layout property), an unset one defaults to
          ``"auto"`` (roofline-tuned,
          :func:`apex_tpu.pyprof.tune_bucket_bytes`); pass
          ``bucket_bytes=`` to pin it explicitly;
        - ``remat_policy="selective"`` — GEMM/flash outputs resident,
          only the cheap LN/gelu tier recomputed (apex_tpu/remat.py);
        - ``sequence_parallel`` + ``tp_comm_overlap`` — ring-decomposed
          TP collectives riding under their GEMMs — when the mesh can
          carry them: ``tp > 1`` and ``pp == 1`` (the SP head/stage
          contract).

        Donation is the trainer-call half of the preset —
        ``jit_train_step(donate=True)`` is already the default. Returns
        a new config; the receiver is unchanged. Explicit model-level
        SP/overlap or remat settings on the receiver are kept as-is.
        Raises for optimizers with no ZeRO variant (sgd/novograd/...).
        """
        if not _zero_enabled(self.optimizer.zero) \
                and self.optimizer.name not in ZERO_CAPABLE_OPTIMIZERS:
            raise ValueError(
                f"fastpath needs a ZeRO-capable optimizer "
                f"({'|'.join(ZERO_CAPABLE_OPTIMIZERS)}), got "
                f"{self.optimizer.name!r}")
        tp = self.parallel.tensor_model_parallel_size
        pp = self.parallel.pipeline_model_parallel_size
        sp_ok = tp > 1 and pp == 1
        # the deprecated remat=True spelling means "full" (ModelConfig
        # docs) — a receiver that asked for full remat keeps it; only a
        # genuinely-unset policy defaults to selective
        policy = self.model.remat_policy or (
            "full" if self.model.remat else "selective")
        model = dataclasses.replace(
            self.model,
            remat_policy=policy,
            sequence_parallel=self.model.sequence_parallel or sp_ok,
            tp_comm_overlap=self.model.tp_comm_overlap or sp_ok)
        optimizer = (self.optimizer if _zero_enabled(self.optimizer.zero)
                     else dataclasses.replace(self.optimizer, zero=1))
        if bucket_bytes is TrainConfig._KEEP:
            bucket_bytes = (self.ddp_bucket_bytes
                            if self.ddp_bucket_bytes is not None
                            else "auto")
        return dataclasses.replace(self, model=model, optimizer=optimizer,
                                   ddp_bucket_bytes=bucket_bytes)

    # -- builders ---------------------------------------------------------
    def build_policy(self):
        import jax.numpy as jnp

        from apex_tpu.amp import get_policy
        half = jnp.bfloat16 if self.half_dtype == "bfloat16" else jnp.float16
        return get_policy(self.opt_level, half_dtype=half)

    def build_scaler(self):
        """Loss-scale object implied by the policy (may be a no-op)."""
        from apex_tpu.amp import make_loss_scale
        return make_loss_scale(self.build_policy().loss_scale)

    def build_health(self):
        """The numerics-watchdog policy (level "off" by default — the
        provably-free tier)."""
        from apex_tpu.observability.health import HealthConfig
        return HealthConfig(level=self.health_level,
                            on_nonfinite=self.health_on_nonfinite,
                            consecutive=self.health_consecutive,
                            dump_dir=self.health_dump_dir)

    def build_model(self):
        import jax.numpy as jnp

        pol = self.build_policy()
        m = self.model
        if m.name == "gpt":
            from apex_tpu.models import GPTConfig, GPTModel
            return GPTModel(GPTConfig(
                vocab_size=m.vocab_size, hidden_size=m.hidden_size,
                num_layers=m.num_layers,
                num_attention_heads=m.num_attention_heads,
                max_position_embeddings=m.max_position_embeddings,
                ffn_hidden_size=m.ffn_hidden_size,
                tensor_model_parallel_size=
                self.parallel.tensor_model_parallel_size,
                params_dtype=pol.param_dtype,
                compute_dtype=pol.compute_dtype,
                hidden_dropout=m.hidden_dropout,
                attention_dropout=m.attention_dropout, remat=m.remat,
                remat_policy=m.remat_policy, remat_names=m.remat_names,
                sequence_parallel=m.sequence_parallel,
                tp_comm_overlap=m.tp_comm_overlap))
        if m.name == "bert":
            from apex_tpu.models import BertConfig, BertModel
            return BertModel(BertConfig(
                vocab_size=m.vocab_size, hidden_size=m.hidden_size,
                num_layers=m.num_layers,
                num_attention_heads=m.num_attention_heads,
                max_position_embeddings=m.max_position_embeddings,
                remat=m.remat, remat_policy=m.remat_policy,
                remat_names=m.remat_names,
                compute_dtype=pol.compute_dtype))
        if m.name == "resnet50":
            from apex_tpu.models import ResNet50, ResNetConfig
            return ResNet50(ResNetConfig(
                num_classes=m.num_classes, compute_dtype=pol.compute_dtype,
                params_dtype=pol.param_dtype))
        raise ValueError(f"unknown model {m.name!r}")

    def build_optimizer(self):
        from apex_tpu import optimizers as opt

        o = self.optimizer
        if _zero_enabled(o.zero):
            if self.ddp_bucket_bytes == "auto":
                # the roofline resolution needs a model + mesh to price;
                # GPTHybridTrainer owns it (and stores the resolved int
                # back into its config). A raw build cannot guess a grid
                # silently — bucket_bytes is a checkpoint-layout property.
                raise ValueError(
                    'ddp_bucket_bytes="auto" must be resolved before '
                    "build_optimizer: construct the trainer "
                    "(GPTHybridTrainer resolves it via "
                    "apex_tpu.pyprof.tune_bucket_bytes) or call "
                    "tune_bucket_bytes yourself and pass the int")
            if o.name in ("adam", "adamw"):
                return opt.DistributedFusedAdam(
                    lr=o.lr, betas=o.betas, eps=o.eps,
                    adam_w_mode=o.name == "adamw",
                    weight_decay=o.weight_decay,
                    bucket_bytes=self.ddp_bucket_bytes)
            if o.name == "lamb":
                return opt.DistributedFusedLAMB(
                    lr=o.lr, betas=o.betas, eps=o.eps,
                    weight_decay=o.weight_decay,
                    bucket_bytes=self.ddp_bucket_bytes)
            # dispatch above covers exactly ZERO_CAPABLE_OPTIMIZERS —
            # extend both together (fastpath() gates on the same tuple)
            raise ValueError(
                f"no ZeRO variant of {o.name!r} (capable: "
                f"{'|'.join(ZERO_CAPABLE_OPTIMIZERS)})")
        if o.name in ("adam", "adamw"):
            inner = opt.FusedAdam(lr=o.lr, betas=o.betas, eps=o.eps,
                                  adam_w_mode=o.name == "adamw",
                                  weight_decay=o.weight_decay)
        elif o.name == "sgd":
            inner = opt.FusedSGD(lr=o.lr, momentum=o.momentum,
                                 weight_decay=o.weight_decay)
        elif o.name == "lamb":
            inner = opt.FusedLAMB(lr=o.lr, betas=o.betas, eps=o.eps,
                                  weight_decay=o.weight_decay)
        elif o.name == "novograd":
            inner = opt.FusedNovoGrad(lr=o.lr, betas=o.betas, eps=o.eps,
                                      weight_decay=o.weight_decay)
        elif o.name == "adagrad":
            inner = opt.FusedAdagrad(lr=o.lr,
                                     weight_decay=o.weight_decay)
        else:
            raise ValueError(f"unknown optimizer {o.name!r}")
        return opt.FlatOptimizer(inner) if o.flat else inner

    def build_microbatch_calculator(self, data_parallel_size: int):
        from apex_tpu.transformer.pipeline_parallel.microbatches import (
            build_num_microbatches_calculator)
        ram = (list(self.batch.rampup_batch_size)
               if self.batch.rampup_batch_size else None)
        return build_num_microbatches_calculator(
            rank=0, rampup_batch_size=ram,
            global_batch_size=self.batch.global_batch_size,
            micro_batch_size=self.batch.micro_batch_size,
            data_parallel_size=data_parallel_size)

    def build_sampler(self, total_samples: int, consumed_samples: int,
                      data_parallel_rank: int, data_parallel_size: int,
                      shuffle: bool = False):
        from apex_tpu.transformer._data import (
            MegatronPretrainingRandomSampler, MegatronPretrainingSampler)
        local = self.batch.global_batch_size // data_parallel_size
        cls = (MegatronPretrainingRandomSampler if shuffle
               else MegatronPretrainingSampler)
        return cls(total_samples=total_samples,
                   consumed_samples=consumed_samples,
                   local_minibatch_size=local,
                   data_parallel_rank=data_parallel_rank,
                   data_parallel_size=data_parallel_size)

    def initialize_mesh(self, devices=None):
        from apex_tpu.transformer import parallel_state
        return parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=
            self.parallel.tensor_model_parallel_size,
            pipeline_model_parallel_size=
            self.parallel.pipeline_model_parallel_size,
            virtual_pipeline_model_parallel_size=
            self.parallel.virtual_pipeline_model_parallel_size,
            context_parallel_size=self.parallel.context_parallel_size,
            devices=devices,
            dcn_data_parallel=self.parallel.dcn_data_parallel)

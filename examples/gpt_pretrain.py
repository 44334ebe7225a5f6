"""GPT pretraining with hybrid TP x PP x DP — the ``reference:tests/L0/
run_transformer/run_gpt_minimal_test.py`` / ``gpt_scaling_test.py`` role
as a runnable example.

Drives the whole model-parallel toolkit from one config: vocab-parallel
embedding pipelined on stage 0, tied head + vocab-parallel loss on the
last stage, tensor-parallel layers inside each stage, data-parallel grad
averaging, Megatron sampler feeding token batches, MP-synced dynamic loss
scaling, and checkpointing.

    python examples/gpt_pretrain.py --tp 2 --pp 2 --steps 5
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                             ParallelConfig, TrainConfig)
from apex_tpu.training import GPTHybridTrainer
from apex_tpu.transformer import parallel_state


def main(argv=None, on_metrics=None):
    """``on_metrics`` (tests): called with the server's base URL while
    the ``--metrics-port`` endpoint is still live, after training."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--tp", type=int, default=2)
    ap.add_argument("--pp", type=int, default=2)
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--layers-per-stage", type=int, default=2)
    ap.add_argument("--seq", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--micro-batch", type=int, default=2)
    ap.add_argument("--num-micro", type=int, default=4)
    ap.add_argument("--zero", action="store_true",
                    help="shard optimizer state 1/dp over the data axis "
                         "(DistributedFusedAdam; reduce_scatter grads, "
                         "all_gather params)")
    ap.add_argument("--bucket-bytes", type=int, default=None,
                    help="DP-sync bucket size in bytes: route the grad "
                         "sync (and the ZeRO reduce_scatter/all_gather) "
                         "through the bucketed overlap engine in B "
                         "fixed-size flat fp32 buckets (docs/PERF.md "
                         "'DP overlap + ZeRO'; default: unbucketed)")
    ap.add_argument("--remat-policy", default=None,
                    choices=["none", "full", "selective", "offload"],
                    help="activation rematerialization policy "
                         "(apex_tpu/remat.py): selective keeps the "
                         "registry-tagged GEMM/flash outputs resident "
                         "and recomputes only the LN/gelu tier "
                         "(docs/PERF.md 'Remat & HBM')")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="enable the elastic runtime "
                         "(apex_tpu/elastic/): async checkpoints to this "
                         "dir every --save-interval steps, SIGTERM/"
                         "APEX_TPU_TERMINATE preemption handling (drain "
                         "+ final save + exit 0), and automatic bitwise "
                         "resume from the latest COMMITTED checkpoint "
                         "(docs/ROBUSTNESS.md)")
    ap.add_argument("--keep-last", type=int, default=3,
                    help="checkpoint GC depth: keep the newest N "
                         "COMMITTED checkpoints (torn dirs are never "
                         "GC'd)")
    ap.add_argument("--save-interval", type=int, default=2,
                    help="steps between async checkpoints")
    ap.add_argument("--sequence-parallel", action="store_true",
                    help="Megatron-LM sequence parallelism (tp > 1, "
                         "pp == 1)")
    ap.add_argument("--tp-comm-overlap", action="store_true",
                    help="ring-decomposed SP collectives overlapping "
                         "their GEMMs (implies --sequence-parallel; see "
                         "docs/PERF.md)")
    ap.add_argument("--fastpath", action="store_true",
                    help="the compound overlap preset "
                         "(TrainConfig.fastpath): ZeRO-1 with "
                         "backward-interleaved per-bucket RS/AG chains, "
                         "roofline-autotuned DP buckets "
                         "(--bucket-bytes overrides), selective remat, "
                         "and — at tp>1, pp==1 on VMA jax — "
                         "sequence-parallel tp_comm_overlap "
                         "(docs/PERF.md 'Flagship tuning')")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the local metrics registry over HTTP "
                         "while training: /metrics in Prometheus text "
                         "exposition (registry.render_prometheus — the "
                         "single-process face of the fleet endpoint the "
                         "elastic supervisor serves; docs/"
                         "OBSERVABILITY.md 'Fleet observability'); 0 "
                         "picks an ephemeral port")
    args = ap.parse_args(argv)
    if args.tp_comm_overlap:
        args.sequence_parallel = True

    tp, pp = args.tp, args.pp
    dp = jax.device_count() // (tp * pp)
    M, mb, seq = args.num_micro, args.micro_batch, args.seq
    cfg = TrainConfig(
        model=ModelConfig(name="gpt", vocab_size=args.vocab,
                          hidden_size=args.hidden,
                          num_layers=args.layers_per_stage * pp,
                          num_attention_heads=4,
                          max_position_embeddings=seq,
                          remat_policy=args.remat_policy,
                          sequence_parallel=args.sequence_parallel,
                          tp_comm_overlap=args.tp_comm_overlap),
        parallel=ParallelConfig(tensor_model_parallel_size=tp,
                                pipeline_model_parallel_size=pp),
        batch=BatchConfig(global_batch_size=M * mb * dp,
                          micro_batch_size=mb),
        optimizer=OptimizerConfig(name="adam", lr=1e-3, weight_decay=0.0,
                                  zero=args.zero),
        opt_level="O0", ddp_bucket_bytes=args.bucket_bytes)
    if args.fastpath:
        # one declarative preset over the flags above; an explicit
        # --bucket-bytes (already in the config) is kept, otherwise the
        # pyprof roofline resolves "auto" at trainer construction
        cfg = cfg.fastpath()

    server = metrics_registry = None
    if args.metrics_port is not None:
        # the single-process face of the supervisor's fleet endpoint:
        # serve THIS process's registry (render_prometheus) — same route,
        # no aggregation layer needed at world size 1
        from apex_tpu.observability import get_registry
        from apex_tpu.observability.fleet import MetricsServer
        metrics_registry = get_registry()
        server = MetricsServer(metrics_registry.render_prometheus,
                               port=args.metrics_port)
        port = server.start()
        print(f"serving /metrics on http://127.0.0.1:{port}/metrics")

    def _finish(result):
        if server is not None:
            if on_metrics is not None:
                on_metrics(server.url)
            server.close()
        return result

    # everything below runs under the server's try/finally:
    # the exception path must not leak the listening socket
    # (_finish already closed it on the success paths; close()
    # is idempotent)
    try:
        mesh = cfg.initialize_mesh()
        trainer = GPTHybridTrainer(cfg, mesh)
        calc = cfg.build_microbatch_calculator(dp)
        assert calc.get() == M
        rng = np.random.RandomState(0)
        data = rng.randint(0, args.vocab, (10_000, seq + 1))

        if args.checkpoint_dir:
            # elastic path: seeded resumable sharded data + async checkpoints
            # + preemption-safe loop; restart the same command line to resume
            from jax.sharding import NamedSharding, PartitionSpec as P

            from apex_tpu.elastic import (ElasticRunner, PrefetchingIterator,
                                          ShardedIndexIterator,
                                          token_batch_fetcher)
            it = PrefetchingIterator(
                ShardedIndexIterator(10_000, M * dp * mb, seed=0),
                token_batch_fetcher(data, M, dp * mb, seq), depth=2,
                sharding=NamedSharding(mesh, P(None, "data")))
            try:
                runner = ElasticRunner(
                    trainer, it, args.checkpoint_dir,
                    save_interval=args.save_interval,
                    keep_last=args.keep_last,
                    on_step=lambda k, loss: print(f"step {k}: loss "
                                                  f"{float(loss):.4f}"))
                res = runner.fit(args.steps, key=jax.random.PRNGKey(0))
            finally:
                parallel_state.destroy_model_parallel()
            return _finish(res.loss)

        state = list(trainer.init_state(jax.random.PRNGKey(0)))

        # Megatron sampler drives the host data order
        sampler = cfg.build_sampler(total_samples=10_000, consumed_samples=0,
                                    data_parallel_rank=0, data_parallel_size=1,
                                    shuffle=True)
        batches = iter(sampler)

        # donated jit: stage/shared/opt_state update in place — the loop below
        # only ever touches the returned state, never a consumed buffer
        step_fn = trainer.jit_train_step()
        loss = None
        try:
            for i in range(args.steps):
                # one sampler batch == one global batch (M * dp * mb rows);
                # native memcpy row-gather packs it
                from apex_tpu._native import gather_rows
                rows = next(batches)
                chunk = gather_rows(data, rows).reshape(M, dp * mb, seq + 1)
                tokens = jnp.asarray(chunk[..., :-1])
                targets = jnp.asarray(chunk[..., 1:])
                loss, *state = step_fn(*state, tokens, targets)
                if metrics_registry is not None:
                    metrics_registry.counter("train/steps").inc()
                ls = state[-1]
                print(f"step {i}: loss {float(loss):.4f} "
                      f"scale {float(ls.loss_scale):.0f}")
        finally:
            parallel_state.destroy_model_parallel()
        return _finish(float(loss))
    finally:
        if server is not None:
            server.close()


if __name__ == "__main__":
    main()

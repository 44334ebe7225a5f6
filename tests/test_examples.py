"""Smoke tests: the examples/ scripts (the reference's L5 layer) must run
end to end on the CPU mesh."""

import json
import sys

import numpy as np

import pytest

sys.path.insert(0, "examples")


def test_simple_distributed_runs():
    import simple_distributed
    loss = simple_distributed.main(steps=15)
    assert loss < 1.0


def test_imagenet_amp_runs_and_resumes(tmp_path):
    import imagenet_amp
    first = imagenet_amp.main(["--steps", "2", "--per-device-batch", "1",
                               "--img", "32", "--opt-level", "O2",
                               "--ckpt-dir", str(tmp_path)])
    assert np.isfinite(first)
    # resume picks up at step 2
    loss = imagenet_amp.main(["--steps", "1", "--per-device-batch", "1",
                              "--img", "32", "--opt-level", "O2",
                              "--ckpt-dir", str(tmp_path)])
    assert np.isfinite(loss)


def test_gpt_pretrain_runs_and_serves_metrics_port():
    """The pretrain example, also exercising --metrics-port (one run,
    not two — tier-1 budget): the single-process face of the fleet
    endpoint. /metrics serves the LOCAL registry in Prometheus text
    exposition over a real HTTP round-trip while the server is live
    (port 0 = ephemeral), carrying the train-side step counter."""
    import urllib.request

    import gpt_pretrain

    from apex_tpu.observability import get_registry

    get_registry().counter("train/steps").reset()
    seen = {}

    def fetch(base_url):
        with urllib.request.urlopen(base_url + "/metrics",
                                    timeout=10) as r:
            seen["status"] = r.status
            seen["text"] = r.read().decode()

    loss = gpt_pretrain.main(["--tp", "2", "--pp", "2", "--steps", "2",
                              "--metrics-port", "0"], on_metrics=fetch)
    assert loss > 0
    assert seen["status"] == 200
    text = seen["text"]
    assert "train_steps 2" in text
    # parses as Prometheus text exposition: every sample line is
    # "name value" with a float-spellable value
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, _, value = line.rpartition(" ")
        assert name, line
        float(value)


def test_gpt_pretrain_zero_runs():
    """--zero swaps in the ZeRO sharded optimizer (DistributedFusedAdam)
    inside the same hybrid trainer — here with --bucket-bytes, so the
    example drives the per-bucket reduce_scatter/all_gather overlap path;
    the loss trajectory must stay finite and positive."""
    import gpt_pretrain
    loss = gpt_pretrain.main(["--tp", "2", "--pp", "2", "--steps", "2",
                              "--zero", "--bucket-bytes", "4096"])
    assert loss > 0


def test_gpt_pretrain_elastic_checkpoint_and_resume(tmp_path):
    """--checkpoint-dir routes the example through the elastic runtime:
    the first invocation checkpoints as it trains, the second resumes
    from the latest COMMITTED step and runs only the remaining steps."""
    import gpt_pretrain

    from apex_tpu.checkpoint import all_steps, latest_step

    args = ["--tp", "2", "--pp", "2", "--checkpoint-dir", str(tmp_path),
            "--save-interval", "1", "--keep-last", "2"]
    loss = gpt_pretrain.main(args + ["--steps", "2"])
    assert np.isfinite(loss)
    assert latest_step(str(tmp_path)) == 2
    assert len(all_steps(str(tmp_path))) <= 2  # keep_last GC bound
    loss2 = gpt_pretrain.main(args + ["--steps", "3"])
    assert np.isfinite(loss2)
    assert latest_step(str(tmp_path)) == 3


def test_gpt_serve_runs(tmp_path):
    """The serving demo: every request completes through the continuous
    batcher, the serve/* surface is populated, and the
    percentile/goodput summary plus
    the per-slot Chrome request trace come out (docs/SERVING.md)."""
    import gpt_serve
    trace_path = tmp_path / "req_trace.json"
    payload = gpt_serve.main(["--requests", "4", "--max-new-tokens", "4",
                              "--trace-out", str(trace_path)])
    results = payload["completions"]
    assert sorted(results) == list(range(4))
    for i, c in sorted(results.items()):
        assert len(c.tokens) == 1 + (4 * (i + 1)) // 2
        assert c.finish_reason == "length"
        # completions carry the measured request latencies
        assert c.queue_wait_ms >= 0.0
        assert c.ttft_ms >= c.queue_wait_ms
        assert c.e2e_ms >= c.ttft_ms and c.tpot_ms > 0.0
    m = payload["metrics"]
    assert m["serve/admitted"] == 4.0 and m["serve/retired"] == 4.0
    assert m["serve/generated_tokens"] == sum(
        1 + (4 * (i + 1)) // 2 for i in range(4))
    assert m["serve/tokens_per_sec"] > 0.0
    # the latency/SLO summary: p50 <= p95 <= p99, all measured
    lat = payload["latency"]
    for short in ("ttft", "tpot", "queue_wait", "e2e"):
        p50, p95, p99 = (lat[f"{short}_p{q}_ms"] for q in (50, 95, 99))
        assert 0.0 <= p50 <= p95 <= p99, short
    assert lat["ttft_p50_ms"] > 0.0
    assert 0.0 <= payload["goodput"] <= 1.0
    assert payload["slo"] and "ttft_ms p95" in payload["slo"][0]
    # the Chrome request trace is strict JSON with per-slot lanes
    doc = json.loads(trace_path.read_text())
    lanes = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert lanes == {"queue", "slot 0", "slot 1"}
    # resilience counts ride the payload: nothing rejected or expired
    # in an unconstrained run
    assert payload["rejected"] == 0 and payload["expired"] == 0


def test_gpt_serve_resilience_flags():
    """--max-queue bounds admission with typed rejections and
    --deadline-ms expires overdue requests — the counts the demo prints
    (docs/SERVING.md "Resilience")."""
    import gpt_serve
    # every request is submitted before the loop starts, so a 6-request
    # run against --max-queue 2 deterministically rejects 4
    payload = gpt_serve.main(["--requests", "6", "--max-new-tokens", "2",
                              "--max-queue", "2"])
    assert payload["rejected"] == 4 and payload["expired"] == 0
    assert [r.reason for r in payload["rejections"]] == ["queue_full"] * 4
    assert len(payload["completions"]) == 2  # the two that fit served
    # a microscopic default deadline expires everything in the queue
    payload = gpt_serve.main(["--requests", "3", "--max-new-tokens", "2",
                              "--deadline-ms", "0.001"])
    assert payload["expired"] == 3 and payload["rejected"] == 0
    assert all(c.finish_reason == "expired"
               for c in payload["completions"].values())


def test_gpt_serve_speculative_flag():
    """--speculate-k serves the same request mix through the verify
    program and prints the acceptance rate plus the TPOT delta against
    a same-session non-speculative baseline (docs/SERVING.md
    "Speculative decoding"). Greedy requests must complete with their
    exact lengths — speculation changes the stepping, never the
    stream."""
    import gpt_serve
    payload = gpt_serve.main(["--requests", "4", "--max-new-tokens", "6",
                              "--speculate-k", "3"])
    results = payload["completions"]
    assert sorted(results) == list(range(4))
    for i, c in sorted(results.items()):
        assert len(c.tokens) == 1 + (6 * (i + 1)) // 2
        assert c.finish_reason == "length"
    spec = payload["spec"]
    assert spec["k"] == 3
    assert 0.0 <= spec["accept_rate"] <= 1.0
    assert spec["drafted"] > 0 and spec["spec_steps"] > 0
    assert spec["accepted"] == round(spec["accept_rate"]
                                     * spec["drafted"])
    # the A/B carries both TPOT medians and their delta
    assert spec["tpot_p50_ms"] > 0.0 and spec["baseline_tpot_p50_ms"] > 0.0
    assert spec["tpot_delta_ms"] == round(
        spec["baseline_tpot_p50_ms"] - spec["tpot_p50_ms"], 2)
    # without the flag the payload says so explicitly
    assert gpt_serve.main(["--requests", "2",
                           "--max-new-tokens", "2"])["spec"] is None


def test_dcgan_amp_runs():
    import dcgan_amp
    errD, errG = dcgan_amp.main(["--steps", "3", "--batch", "8"])
    assert np.isfinite(errD) and np.isfinite(errG)


def test_long_context_example_runs():
    import long_context
    val = long_context.main(["--seq-per-device", "64"])
    assert np.isfinite(val)


def test_telemetry_example_runs(tmp_path):
    """The observability worked example: 3 steps must stream the full
    documented metric surface and a loadable Chrome trace."""
    import telemetry
    payload = telemetry.main(["--steps", "3", "--out-dir", str(tmp_path)])
    for key in ("loss", "amp/loss_scale", "ddp/allreduce_bytes",
                "optim/grad_norm", "pipeline/bubble_fraction"):
        assert key in payload
    assert (tmp_path / "telemetry.jsonl").exists()
    assert (tmp_path / "host_trace.json").exists()
    # the health-watchdog demo ran: the injected inf produced an
    # attributed crash dump
    dumps = list(tmp_path.glob("health_dump_step*.json"))
    assert len(dumps) == 1
    doc = json.loads(dumps[0].read_text())
    assert doc["attribution"] == {"grads": "['bad']"}
    assert doc["metrics"]["health/grads/nonfinite_count"] == 2.0

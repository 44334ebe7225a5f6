"""pyprof reborn: the per-region step-time attribution engine.

Covers the roofline cost model (`pyprof/model.py`) — per-primitive FLOP
pricing against XLA's counting conventions, ring-model collective wire
bytes, scan/pallas multipliers, `named_scope` region bucketing — the
trace-join layer (`pyprof/_attribute.py`), the `StepReporter.
attach_attribution` gauge surface, the bench/script wiring, and the
acceptance smoke: a real (tiny) GPT train step whose modeled FLOPs must
match `costs.flops_budget(compiled)` and whose every region is known to
the `scripts/check_annotations.py` contract.
"""

import ast
import gzip
import importlib.util
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu import observability as obs
from apex_tpu import pyprof
from apex_tpu.observability.costs import (DEFAULT_DEVICE_SPEC, DeviceSpec,
                                          device_spec, flops_budget)
from apex_tpu.pyprof import (DEFAULT_REGIONS, UNATTRIBUTED,
                             AttributionReport, attribute, model_program)
from jax import shard_map

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _mesh(n, axis="x"):
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), (axis,))


# ---------------------------------------------------------------------------
# DeviceSpec table
# ---------------------------------------------------------------------------

class TestDeviceSpec:
    def test_table_lookup_by_kind_prefix(self):
        class Fake:
            def __init__(self, kind):
                self.device_kind = kind

        v5p = device_spec(Fake("TPU v5p"))
        assert v5p.peak_flops == 459e12 and v5p.hbm_gbps == 2765.0
        v5e = device_spec(Fake("TPU v5 lite something"))
        assert v5e.peak_flops == 197e12
        # CPU hosts fall back to the conservative v5e-class default
        assert device_spec(Fake("cpu")) is DEFAULT_DEVICE_SPEC
        assert device_spec() is DEFAULT_DEVICE_SPEC  # CPU test host

    def test_env_overrides(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_HBM_GBPS", "100.0")
        spec = device_spec()
        assert spec.hbm_gbps == 100.0
        assert spec.peak_flops == DEFAULT_DEVICE_SPEC.peak_flops
        assert "env-tuned" in spec.name
        monkeypatch.setenv("APEX_TPU_HBM_GBPS", "-3")
        with pytest.raises(ValueError):
            device_spec()

    def test_roofline_ms(self):
        spec = DeviceSpec("t", peak_flops=1e12, hbm_gbps=1.0, ici_gbps=2.0)
        assert spec.compute_ms(1e12) == pytest.approx(1e3)
        assert spec.hbm_ms(1e9) == pytest.approx(1e3)
        assert spec.comm_ms(1e9) == pytest.approx(500.0)


# ---------------------------------------------------------------------------
# the roofline walker
# ---------------------------------------------------------------------------

class TestModelProgram:
    def test_dot_general_flops_and_hbm(self):
        a, b = jnp.ones((8, 16)), jnp.ones((16, 4))
        cost = model_program(lambda a, b: a @ b, (a, b))
        assert cost.flops == 2 * 8 * 16 * 4
        # operands + result, fp32
        assert cost.hbm_bytes == (8 * 16 + 16 * 4 + 8 * 4) * 4
        assert list(cost.regions) == [UNATTRIBUTED]

    def test_named_scope_bucketing_innermost_wins(self):
        def f(x, w):
            with jax.named_scope("gpt_attention"):
                x = x @ w
                with jax.named_scope("flash_attention"):
                    x = x @ w
            with jax.named_scope("gpt_mlp"):
                return x @ w

        x, w = jnp.ones((4, 8)), jnp.ones((8, 8))
        cost = model_program(f, (x, w))
        per_mm = 2 * 4 * 8 * 8
        assert cost.regions["gpt_attention"].flops == per_mm
        assert cost.regions["flash_attention"].flops == per_mm  # carved out
        assert cost.regions["gpt_mlp"].flops == per_mm

    def test_region_names_survive_grad_transform(self):
        def loss(w, x):
            with jax.named_scope("gpt_mlp"):
                return jnp.sum((x @ w) ** 2)

        w, x = jnp.ones((8, 8)), jnp.ones((4, 8))
        cost = model_program(jax.grad(loss), (w, x))
        # the fwd matmul AND the transposed dW matmul both bucket to the
        # region through the transpose(jvp(...)) name-stack wrappers
        assert cost.regions["gpt_mlp"].flops >= 2 * (2 * 4 * 8 * 8)

    def test_scan_multiplies_by_trip_count(self):
        w = jnp.ones((8, 8))

        def scanned(x):
            return jax.lax.scan(lambda c, _: (c @ w, None), x,
                                None, length=5)[0]

        x = jnp.ones((4, 8))
        cost = model_program(scanned, (x,))
        once = model_program(lambda x: x @ w, (x,))
        assert cost.flops == 5 * once.flops

    def test_transcendentals_excluded_elementwise_counted(self):
        x = jnp.ones((16, 16))
        cost = model_program(lambda x: jnp.tanh(x + x), (x,))
        assert cost.flops == 16 * 16  # the add; tanh books zero

    def test_bound_classification(self):
        a, b = jnp.ones((64, 64)), jnp.ones((64, 64))
        starved = DeviceSpec("starved", peak_flops=1.0, hbm_gbps=1e9,
                             ici_gbps=1e9)
        cost = model_program(lambda a, b: a @ b, (a, b), spec=starved)
        assert cost.regions[UNATTRIBUTED].bound == "compute"
        choked = DeviceSpec("choked", peak_flops=1e30, hbm_gbps=1e-9,
                            ici_gbps=1e9)
        cost = model_program(lambda a, b: a @ b, (a, b), spec=choked)
        assert cost.regions[UNATTRIBUTED].bound == "memory"

    def test_callable_without_args_raises(self):
        with pytest.raises(TypeError):
            model_program(lambda x: x)


class TestCollectivePricing:
    """Ring-model ICI wire bytes per rank, axis sizes read off the
    enclosing shard_map's mesh."""

    def test_psum_prices_two_n_minus_one_over_n(self):
        mesh = _mesh(4)
        g = shard_map(lambda x: jax.lax.psum(x, "x"), mesh=mesh,
                      in_specs=P("x"), out_specs=P())
        cost = model_program(jax.make_jaxpr(g)(jnp.ones((8, 4))))
        shard_bytes = 2 * 4 * 4
        assert cost.comm_bytes == pytest.approx(2 * shard_bytes * 3 / 4)

    def test_all_gather_prices_n_minus_one_shards(self):
        mesh = _mesh(4)
        g = shard_map(lambda x: jax.lax.all_gather(x, "x"), mesh=mesh,
                      in_specs=P("x"), out_specs=P(), check_rep=False)
        cost = model_program(jax.make_jaxpr(g)(jnp.ones((8, 4))))
        assert cost.comm_bytes == pytest.approx((2 * 4 * 4) * 3)

    def test_psum_scatter_prices_n_minus_one_over_n(self):
        mesh = _mesh(4)
        g = shard_map(lambda x: jax.lax.psum_scatter(x, "x"), mesh=mesh,
                      in_specs=P(), out_specs=P("x"), check_rep=False)
        cost = model_program(jax.make_jaxpr(g)(jnp.ones((4, 8))))
        assert cost.comm_bytes == pytest.approx((4 * 8 * 4) * 3 / 4)

    def test_ppermute_prices_one_hop(self):
        mesh = _mesh(4)
        perm = [(i, (i + 1) % 4) for i in range(4)]
        g = shard_map(lambda x: jax.lax.ppermute(x, "x", perm), mesh=mesh,
                      in_specs=P("x"), out_specs=P("x"))
        cost = model_program(jax.make_jaxpr(g)(jnp.ones((8, 4))))
        assert cost.comm_bytes == pytest.approx(2 * 4 * 4)  # one shard

    def test_ring_chain_prices_hop_by_hop(self):
        """tp-1 scanned ppermutes (the PR-2 collective-matmul shape)
        price as tp-1 hops — the same traffic as the fused gather they
        replace."""
        mesh = _mesh(4)
        perm = [(i, (i + 1) % 4) for i in range(4)]

        def ring(x):
            def body(c, _):
                return jax.lax.ppermute(c, "x", perm), None
            return jax.lax.scan(body, x, None, length=3)[0]

        g = shard_map(ring, mesh=mesh, in_specs=P("x"), out_specs=P("x"))
        chain = model_program(jax.make_jaxpr(g)(jnp.ones((8, 4))))
        gather = model_program(jax.make_jaxpr(
            shard_map(lambda x: jax.lax.all_gather(x, "x"), mesh=mesh,
                      in_specs=P("x"), out_specs=P(), check_rep=False)
        )(jnp.ones((8, 4))))
        assert chain.comm_bytes == pytest.approx(gather.comm_bytes)

    def test_collective_hbm_endpoints_counted(self):
        mesh = _mesh(4)
        g = shard_map(lambda x: jax.lax.psum(x, "x"), mesh=mesh,
                      in_specs=P("x"), out_specs=P())
        cost = model_program(jax.make_jaxpr(g)(jnp.ones((8, 4))))
        assert cost.hbm_bytes == pytest.approx(2 * (2 * 4 * 4))


# ---------------------------------------------------------------------------
# region vocabulary <-> annotation contract
# ---------------------------------------------------------------------------

class TestRegionContract:
    def test_default_regions_subset_of_annotations_table(self):
        """Every region the attribution report can name must be a
        named_scope the check_annotations contract proves exists."""
        mod = _load_script("check_annotations")
        assert set(DEFAULT_REGIONS) <= set(mod.ANNOTATIONS)

    def test_annotation_script_passes(self):
        proc = subprocess.run(
            [sys.executable, "scripts/check_annotations.py"], cwd=REPO,
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# attribution join
# ---------------------------------------------------------------------------

def _small_report(step_time_s=0.01, **kw):
    def f(x, w):
        with jax.named_scope("gpt_mlp"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("gpt_head_loss"):
            return jnp.sum(h @ w)

    args = (jnp.ones((16, 32)), jnp.ones((32, 32)))
    return attribute(f, step_time_s, args=args, **kw)


class TestAttribute:
    def test_scaled_apportionment_and_shares(self):
        rep = _small_report()
        assert rep.measured_source == "scaled"
        assert rep.step_time_ms == pytest.approx(10.0)
        assert sum(r.share for r in rep.regions) == pytest.approx(1.0)
        assert sum(r.measured_ms for r in rep.regions) \
            == pytest.approx(10.0)
        # comm-free program: zero exposure, overlap undefined
        assert rep.comm_exposed_ms == 0.0
        assert rep.overlap_efficiency is None
        assert all(r.comm_exposed_ms == 0.0 for r in rep.regions)

    def test_no_step_time_no_measured_columns(self):
        rep = _small_report(step_time_s=None)
        assert rep.measured_source == "none"
        assert rep.step_time_ms is None and rep.comm_exposed_ms is None
        assert all(r.measured_ms is None for r in rep.regions)

    def test_exposure_capped_by_modeled_comm(self):
        """A region measured far beyond its roofline can only blame its
        modeled comm traffic — a comm-free region never reports
        exposure, however slow it measured."""
        mesh = _mesh(4)
        g = shard_map(lambda x: jax.lax.psum(jnp.tanh(x), "x"), mesh=mesh,
                      in_specs=P("x"), out_specs=P())
        jaxpr = jax.make_jaxpr(g)(jnp.ones((8, 4)))
        rep = attribute(jaxpr, 1.0)  # 1000 ms for a microscopic program
        (region,) = [r for r in rep.regions if r.comm_bytes > 0]
        assert region.comm_exposed_ms == pytest.approx(region.comm_ms)
        assert rep.overlap_efficiency == 0.0  # nothing was hidden
        free = _small_report(step_time_s=5.0)
        assert free.comm_exposed_ms == 0.0

    def test_markdown_and_jsonl_render(self):
        rep = _small_report()
        md = rep.markdown()
        assert md.splitlines()[0].startswith("| region |")
        assert "gpt_mlp" in md and "modeled_step_ms=" in md
        lines = rep.json_lines().splitlines()
        objs = [json.loads(l, parse_constant=pytest.fail) for l in lines]
        step = [o for o in objs if o["region"] == "_step"]
        assert len(step) == 1
        assert step[0]["modeled_step_ms"] == pytest.approx(
            rep.modeled_step_ms)
        assert {o["region"] for o in objs} \
            >= {"gpt_mlp", "gpt_head_loss", "_step"}

    def test_xla_flops_cross_check_field(self):
        def f(x, w):
            return jnp.sum(x @ w)

        args = (jnp.ones((16, 32)), jnp.ones((32, 32)))
        traced = jax.jit(f).trace(*args)
        compiled = traced.lower().compile()
        rep = attribute(traced, 0.001, compiled=compiled)
        if rep.xla_flops:  # backend-dependent
            assert rep.flops == pytest.approx(rep.xla_flops, rel=0.05)

    def test_region_times_from_spans(self):
        spans = [obs.Span("step/gpt_mlp", 1.0, 1.25),
                 obs.Span("gpt_mlp", 2.0, 2.05),
                 obs.Span("unrelated", 0.0, 9.0)]
        times = pyprof.region_times_from_spans(spans)
        assert times == {"gpt_mlp": pytest.approx(300.0)}

    def test_region_times_from_trace_dir(self, tmp_path):
        events = {"traceEvents": [
            {"name": "fusion.1", "ph": "X", "ts": 0, "dur": 1500,
             "args": {"tf_op": "gpt_attention/dot_general"}},
            {"name": "gpt_attention.2", "ph": "X", "ts": 0, "dur": 500},
            {"name": "ignored", "ph": "C", "ts": 0, "dur": 999},
        ]}
        sub = tmp_path / "plugins" / "profile"
        sub.mkdir(parents=True)
        with gzip.open(sub / "host.trace.json.gz", "wt") as f:
            json.dump(events, f)
        times = pyprof.region_times_from_trace_dir(str(tmp_path))
        assert times == {"gpt_attention": pytest.approx(2.0)}
        assert pyprof.region_times_from_trace_dir(
            str(tmp_path / "empty")) == {}

    def test_trace_region_times_win_over_scaling(self):
        rep = _small_report(step_time_s=0.01,
                            region_times={"gpt_mlp": 7.5})
        assert rep.measured_source == "trace"
        by_name = {r.name: r for r in rep.regions}
        assert by_name["gpt_mlp"].measured_ms == 7.5
        assert by_name["gpt_head_loss"].measured_ms is None

    def test_span_join_buckets_by_innermost_region(self):
        """The trace/span join must bucket by the INNERMOST known region
        — the same rule the cost model uses — so measured walls land in
        the region that carries the modeled cost (flash_attention inside
        gpt_attention, not the outer phase)."""
        spans = [obs.Span("gpt_attention/flash_attention", 0.0, 0.1),
                 obs.Span("gpt_attention/proj", 0.2, 0.25)]
        times = pyprof.region_times_from_spans(spans)
        assert times == {"flash_attention": pytest.approx(100.0),
                         "gpt_attention": pytest.approx(50.0)}

    def test_trace_dir_join_buckets_by_innermost_region(self, tmp_path):
        events = {"traceEvents": [
            {"name": "fusion.7", "ph": "X", "ts": 0, "dur": 2000,
             "args": {"tf_op": "gpt_attention/flash_attention/custom"}},
        ]}
        sub = tmp_path / "plugins" / "profile"
        sub.mkdir(parents=True)
        with gzip.open(sub / "host.trace.json.gz", "wt") as f:
            json.dump(events, f)
        times = pyprof.region_times_from_trace_dir(str(tmp_path))
        assert times == {"flash_attention": pytest.approx(2.0)}

    def test_trace_dir_steps_normalizes_multi_step_captures(self,
                                                            tmp_path):
        """A profile_trace capture spans several steps; ``steps=`` must
        divide the summed durations so the walls are per-step and the
        exposure cap isn't saturated by a 5x-inflated measurement."""
        events = {"traceEvents": [
            {"name": f"gpt_mlp.{i}", "ph": "X", "ts": i, "dur": 1000}
            for i in range(5)]}
        sub = tmp_path / "plugins" / "profile"
        sub.mkdir(parents=True)
        with gzip.open(sub / "host.trace.json.gz", "wt") as f:
            json.dump(events, f)
        assert pyprof.region_times_from_trace_dir(str(tmp_path)) \
            == {"gpt_mlp": pytest.approx(5.0)}
        assert pyprof.region_times_from_trace_dir(
            str(tmp_path), steps=5) == {"gpt_mlp": pytest.approx(1.0)}
        with pytest.raises(ValueError):
            pyprof.region_times_from_trace_dir(str(tmp_path), steps=0)

    def test_trace_dir_averages_across_device_tracks(self, tmp_path):
        """A multi-chip capture has one process track (pid) per device
        core; the per-chip roofline must join against ONE chip's wall —
        averaged across tracks — not an n_devices-fold sum."""
        events = {"traceEvents": [
            {"name": "gpt_mlp.1", "ph": "X", "ts": 0, "dur": 1000,
             "pid": 1},
            {"name": "gpt_mlp.2", "ph": "X", "ts": 5, "dur": 1000,
             "pid": 1},
            {"name": "gpt_mlp.3", "ph": "X", "ts": 0, "dur": 1400,
             "pid": 2},
        ]}
        sub = tmp_path / "plugins" / "profile"
        sub.mkdir(parents=True)
        with gzip.open(sub / "host.trace.json.gz", "wt") as f:
            json.dump(events, f)
        # pid 1 sums to 2.0 ms, pid 2 to 1.4 ms -> per-chip mean 1.7 ms
        assert pyprof.region_times_from_trace_dir(str(tmp_path)) \
            == {"gpt_mlp": pytest.approx(1.7)}

    def test_empty_spans_fall_through_to_trace_dir(self, tmp_path):
        """A span drain that matches no region (capture off, unrelated
        spans) must not swallow a real --trace-dir capture."""
        events = {"traceEvents": [
            {"name": "gpt_mlp.1", "ph": "X", "ts": 0, "dur": 4000}]}
        sub = tmp_path / "plugins" / "profile"
        sub.mkdir(parents=True)
        with gzip.open(sub / "host.trace.json.gz", "wt") as f:
            json.dump(events, f)
        rep = _small_report(step_time_s=0.01, spans=[],
                            trace_dir=str(tmp_path))
        assert rep.measured_source == "trace"
        by_name = {r.name: r for r in rep.regions}
        assert by_name["gpt_mlp"].measured_ms == pytest.approx(4.0)

    def test_partial_trace_excludes_unmeasured_comm_from_overlap(self):
        """A partial trace (a comm-bearing region's events fused away)
        must not inflate overlap_efficiency: the unmeasured region's
        modeled comm leaves the denominator and the report says so."""
        mesh = _mesh(4)

        def g(x):
            with jax.named_scope("apex_ddp_allreduce"):
                a = jax.lax.psum(jnp.tanh(x), "x")
            with jax.named_scope("tp_row_linear"):
                b = jax.lax.psum(x * x, "x")
            return a + b

        jaxpr = jax.make_jaxpr(shard_map(
            g, mesh=mesh, in_specs=P("x"), out_specs=P()))(
                jnp.ones((8, 4)))
        # walls only for the allreduce region, measured fully exposed;
        # tp_row_linear's events were "fused away"
        full = attribute(jaxpr, 1.0)
        by = {r.name: r for r in full.regions}
        wall = {"apex_ddp_allreduce":
                by["apex_ddp_allreduce"].comm_ms + 1.0}
        rep = attribute(jaxpr, 1.0, region_times=wall)
        assert rep.measured_source == "trace"
        # everything measured was exposed -> 0.0, not diluted toward 1
        # by tp_row_linear's unobserved bytes
        assert rep.overlap_efficiency == pytest.approx(0.0)
        assert any("tp_row_linear" in n for n in rep.notes)


class TestAttachAttribution:
    def test_gauges_set_from_report(self):
        rep = obs.StepReporter([], registry=obs.MetricsRegistry())
        report = _small_report()
        assert rep.attach_attribution(report) is rep
        snap = rep.registry.snapshot()
        assert snap["perf/modeled_step_ms"] == pytest.approx(
            report.modeled_step_ms)
        assert snap["perf/comm_exposed_ms"] == 0.0
        # comm-free program: overlap_efficiency stays unset, not 0/1
        assert "perf/overlap_efficiency" not in snap

    def test_unmeasured_report_leaves_exposure_unset(self):
        rep = obs.StepReporter([], registry=obs.MetricsRegistry())
        rep.attach_attribution(_small_report(step_time_s=None))
        snap = rep.registry.snapshot()
        assert "perf/modeled_step_ms" in snap
        assert "perf/comm_exposed_ms" not in snap


# ---------------------------------------------------------------------------
# mfu zero-step-time guard (regression: first-report wall delta ~0)
# ---------------------------------------------------------------------------

class TestMfuGuard:
    def test_mfu_returns_nan_not_raise(self):
        assert obs.mfu(10.0, 2.0, peak=1.0) == 5.0
        assert math.isnan(obs.mfu(1.0, 0.0, peak=1.0))
        assert math.isnan(obs.mfu(1.0, -0.5, peak=1.0))
        assert math.isnan(obs.mfu(1.0, 1.0, peak=0.0))

    def test_zero_wall_delta_leaves_gauge_unset(self, monkeypatch):
        """Two reports inside one perf_counter tick (fast host) must not
        emit a fabricated utilization — and must not crash the loop."""
        from apex_tpu.observability import report as report_mod

        monkeypatch.setattr(report_mod.time, "perf_counter", lambda: 42.0)
        rep = obs.StepReporter([], registry=obs.MetricsRegistry())
        rep.attach_flops_budget(1e6, peak=1e9)
        p0 = rep.report(0)
        p1 = rep.report(1)  # dt == 0.0 exactly
        assert "perf/mfu" not in p0 and "perf/mfu" not in p1

    def test_attach_flops_budget_still_validates_at_config_time(self):
        rep = obs.StepReporter([], registry=obs.MetricsRegistry())
        with pytest.raises(ValueError):
            rep.attach_flops_budget(0.0)
        with pytest.raises(ValueError):
            rep.attach_flops_budget(1e6, peak=-1.0)


# ---------------------------------------------------------------------------
# the acceptance smoke: a real (tiny) GPT train step
# ---------------------------------------------------------------------------

TINY_GPT = {"hidden_size": 64, "num_layers": 2, "vocab_size": 256,
            "num_attention_heads": 2, "batch": 2, "seq": 32}


@pytest.fixture(scope="module")
def tiny_gpt_attribution():
    attr = _load_script("attribute_step")
    traced, compiled, args, _wrapped = attr.build_gpt(TINY_GPT, False)
    return attribute(traced, 0.05, compiled=compiled)


class TestGPTSmoke:
    def test_modeled_flops_match_xla_budget(self, tiny_gpt_attribution):
        rep = tiny_gpt_attribution
        if not rep.xla_flops:
            pytest.skip("backend reports no cost analysis")
        assert rep.flops == pytest.approx(rep.xla_flops, rel=0.05)

    def test_every_region_is_contract_known(self, tiny_gpt_attribution):
        known = set(_load_script("check_annotations").ANNOTATIONS)
        for r in tiny_gpt_attribution.regions:
            assert r.name == UNATTRIBUTED or r.name in known, r.name

    def test_expected_phases_present_and_dominant(self,
                                                  tiny_gpt_attribution):
        by_name = {r.name: r for r in tiny_gpt_attribution.regions}
        for phase in ("gpt_embed", "gpt_ln", "gpt_attention", "gpt_mlp",
                      "gpt_head_loss", "optimizer_step"):
            assert phase in by_name, phase
        # the unattributed residue (scaler/donation glue) stays small
        total = tiny_gpt_attribution.modeled_step_ms
        resid = by_name.get(UNATTRIBUTED)
        assert resid is None or resid.modeled_ms < 0.25 * total

    def test_region_flops_sum_to_report_total(self, tiny_gpt_attribution):
        rep = tiny_gpt_attribution
        assert sum(r.flops for r in rep.regions) == pytest.approx(
            rep.flops)


def test_attribute_step_script_validates():
    """`python scripts/attribute_step.py --model gpt` (tiny config):
    prints the per-region table and its self-validation against
    flops_budget passes within tolerance."""
    proc = subprocess.run(
        [sys.executable, "scripts/attribute_step.py", "--model", "gpt",
         "--config", json.dumps(TINY_GPT), "--iters", "1",
         "--warmup", "1"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "| region |" in proc.stdout
    assert "validation ok" in proc.stdout


# ---------------------------------------------------------------------------
# bench wiring
# ---------------------------------------------------------------------------

class TestBenchWiring:
    def test_attrib_extra_emits_the_two_columns(self):
        sys.path.insert(0, REPO)
        try:
            import bench
        finally:
            sys.path.remove(REPO)

        def f(x, w):
            with jax.named_scope("gpt_mlp"):
                return jnp.sum(x @ w)

        traced = jax.jit(f).trace(jnp.ones((1024, 1024)),
                                  jnp.ones((1024, 1024)))
        extra = bench._attrib_extra(traced, 5.0)
        assert extra["modeled_step_ms"] > 0
        assert extra["comm_exposed_ms"] == 0.0  # comm-free on one chip
        # never fabricates numbers for an unpriceable program
        assert bench._attrib_extra(object(), 5.0) == {}

    def test_gpt_and_headline_benches_carry_attribution(self):
        """Structural: every headline/GPT _emit call site reaches
        _attrib_extra — the bench lines carry modeled_step_ms."""
        src = ast.parse(open(os.path.join(REPO, "bench.py")).read())
        want = {"bench_headline", "bench_gpt", "bench_gpt_remat",
                "bench_gpt_sp_overlap"}
        seen = set()
        for node in ast.walk(src):
            if isinstance(node, ast.FunctionDef) and node.name in want:
                calls = {c.func.id for c in ast.walk(node)
                         if isinstance(c, ast.Call)
                         and isinstance(c.func, ast.Name)}
                if "_attrib_extra" in calls:
                    seen.add(node.name)
        assert seen == want


# ---------------------------------------------------------------------------
# trainer surface
# ---------------------------------------------------------------------------

# The tp x pp x dp step below runs in a CHILD process, because today it
# ABORTS its process on the CPU backend: `attribution_report` fences its
# warm-up execution on one leaf (`utils.timers.device_fence`) and dispatches
# the timed one while the first is still running on other virtual devices;
# the two executions' in-process collectives cross-block at a rendezvous and
# XLA:CPU terminates after 40 s (with `jax.block_until_ready` on every
# output in the fence's place the same step ran through; PERF.md, PR 22).
# In the test's own process that kills the xdist worker and whatever else it
# was given; a child's death fails this test and nothing else.
_TRAINER_ATTRIBUTION_CHILD = r"""
import json

from apex_tpu.utils.hostmesh import force_virtual_cpu_devices

force_virtual_cpu_devices(8)

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_threefry_partitionable", True)

from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                             ParallelConfig, TrainConfig)
from apex_tpu.pyprof import AttributionReport
from apex_tpu.training import GPTHybridTrainer

tp, pp, dp = 2, 2, 2
M, mb, seq = 2, 2, 8
cfg = TrainConfig(
    model=ModelConfig(name="gpt", vocab_size=64, hidden_size=32,
                      num_layers=2 * pp, num_attention_heads=4,
                      max_position_embeddings=seq),
    parallel=ParallelConfig(tensor_model_parallel_size=tp,
                            pipeline_model_parallel_size=pp),
    batch=BatchConfig(global_batch_size=M * mb * dp, micro_batch_size=mb),
    optimizer=OptimizerConfig(name="adam", lr=1e-2, weight_decay=0.0),
    opt_level="O0")
rng = np.random.RandomState(0)
tokens = jnp.asarray(rng.randint(0, 64, (M, dp * mb, seq)))
targets = jnp.asarray(rng.randint(0, 64, (M, dp * mb, seq)))
trainer = GPTHybridTrainer(cfg, cfg.initialize_mesh(devices=jax.devices()))
state = trainer.init_state(jax.random.PRNGKey(0))
rep = trainer.attribution_report(*state, tokens, targets, iters=1)
assert isinstance(rep, AttributionReport)
print(json.dumps({
    "step_time_ms": rep.step_time_ms,
    "measured_source": rep.measured_source,
    "comm_bytes": sum(r.comm_bytes for r in rep.regions),
    "regions": sorted(r.name for r in rep.regions)}))
"""


def test_hybrid_trainer_attribution_report():
    """GPTHybridTrainer.attribution_report prices the trainer's own
    tp x pp x dp step: every pipeline/TP/DP region shows up and the
    collectives carry wire bytes."""
    proc = subprocess.run(
        [sys.executable, "-c", _TRAINER_ATTRIBUTION_CHILD], cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (
        f"the child exited {proc.returncode}\n{proc.stderr[-3000:]}")
    rep = json.loads(proc.stdout.splitlines()[-1])
    assert rep["step_time_ms"] and rep["step_time_ms"] > 0
    assert rep["measured_source"] == "scaled"
    # the sharded step moves real collective traffic (grad psum at
    # minimum), and the model prices it
    assert rep["comm_bytes"] > 0
    names = set(rep["regions"])
    assert "optimizer_step" in names
    known = set(_load_script("check_annotations").ANNOTATIONS)
    assert names <= known | {UNATTRIBUTED}


# ---------------------------------------------------------------------------
# the attribute shadow (PR 6 accepted-wart, fixed in PR 11)
# ---------------------------------------------------------------------------

def test_attribute_function_not_shadowed_by_submodule():
    """pyprof.attribute must stay the FUNCTION even after the attribution
    submodule is imported. The old pyprof/attribute.py made ``import
    apex_tpu.pyprof.attribute`` rebind the package attribute to the
    module, clobbering the entry point process-wide; the submodule now
    lives at pyprof/_attribute.py with its names re-exported."""
    import importlib

    import apex_tpu.pyprof as pp

    sub = importlib.import_module("apex_tpu.pyprof._attribute")
    assert callable(pp.attribute)
    assert pp.attribute is sub.attribute
    assert pp.AttributionReport is sub.AttributionReport
    # the shadowing module path is gone for good
    with pytest.raises(ModuleNotFoundError):
        importlib.import_module("apex_tpu.pyprof.attribute")
    # and the from-package import keeps resolving to the function
    from apex_tpu.pyprof import attribute as fn
    assert fn is sub.attribute

"""The performance observatory: the append-only ``BenchHistory`` store
(schema, persistence, the ``BENCH_r*.json`` importer), the
rolling-median+MAD ``RegressionDetector`` (flat-noise silence, planted
step fires exactly once, unit-inferred direction pinned against
bench.py's actual emitted units), ``AttributionDiff`` suspect naming,
cost-model drift series/shift alerts + the ``perf/model_drift``
gauges, the CLI exit-code contract, and the round-trip precision
guarantee: a 0.3% delta that the printed 2-decimal display value
quantizes away survives in ``raw_value`` through ``bench.py::_emit``.

The perfwatch module is jax-free on purpose; only the bench round-trip
test touches the jax-importing ``bench`` module.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

from apex_tpu.observability import perfwatch as pw
from apex_tpu.observability.registry import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DUMPS = os.path.join(REPO, "tests", "data")


# ---------------------------------------------------------------------------
# records + schema
# ---------------------------------------------------------------------------

class TestRecordSchema:
    def test_make_record_rounds_display_keeps_raw(self):
        rec = pw.make_record("m", 2047.5139, "imgs/sec", 0.8289,
                             git_sha="s", host="h")
        assert rec["value"] == 2047.51
        assert rec["raw_value"] == 2047.5139
        assert rec["unit"] == "imgs/sec" and rec["vs_baseline"] == 0.8289

    def test_extras_promote_through_the_field_table(self):
        rec = pw.make_record(
            "m", 1.0, "ms", git_sha="s", host="h",
            extras={"config": {"zero": 1}, "modeled_step_ms": 5.0,
                    "mfu": 0.41})
        # table-listed extras become top-level keys; the rest rides
        # under extra — so validate_record stays total over the table
        assert rec["config"] == {"zero": 1}
        assert rec["modeled_step_ms"] == 5.0
        assert rec["extra"] == {"mfu": 0.41}
        pw.validate_record(rec)

    def test_validate_rejects_rogue_and_missing(self):
        rec = pw.make_record("m", 1.0, "ms", git_sha="s", host="h")
        with pytest.raises(ValueError, match="missing"):
            pw.validate_record({k: v for k, v in rec.items()
                                if k != "raw_value"})
        with pytest.raises(ValueError, match="rogue"):
            pw.validate_record(dict(rec, rogue=1))

    def test_provenance_defaults_are_stamped(self):
        rec = pw.make_record("m", 1.0, "ms")
        assert rec["git_sha"] and rec["host"]
        assert "/py%d.%d" % sys.version_info[:2] in rec["host"]


class TestBenchHistory:
    def _rec(self, metric="m", value=1.0, unit="ms", **kw):
        kw.setdefault("git_sha", "s")
        kw.setdefault("host", "h")
        return pw.make_record(metric, value, unit, **kw)

    def test_append_persists_and_reloads(self, tmp_path):
        path = str(tmp_path / "h.jsonl")
        hist = pw.BenchHistory(path)
        hist.append(self._rec("a", 1.0))
        hist.append(self._rec("b", 2.0))
        hist.append(self._rec("a", 3.0))
        back = pw.BenchHistory(path)
        assert len(back) == 3
        assert back.metrics() == ["a", "b"]
        assert [r["raw_value"] for r in back.series("a")] == [1.0, 3.0]

    def test_append_validates_before_writing(self, tmp_path):
        path = str(tmp_path / "h.jsonl")
        hist = pw.BenchHistory(path)
        with pytest.raises(ValueError):
            hist.append({"metric": "m"})
        assert not os.path.exists(path)  # nothing half-written

    def test_corrupt_line_fails_loudly_on_load(self, tmp_path):
        path = tmp_path / "h.jsonl"
        path.write_text("not json\n")
        with pytest.raises(ValueError, match="h.jsonl:1"):
            pw.BenchHistory(str(path))

    def test_importer_ingests_and_is_idempotent(self, tmp_path):
        dump = {"n": 7, "cmd": "bench", "rc": 0, "tail": "\n".join([
            "some log line",
            json.dumps({"metric": "tps", "value": 100.25,
                        "unit": "tokens/sec", "vs_baseline": 0.9,
                        "mfu": 0.4}),
            json.dumps({"metric": "lat", "value": 3.14, "unit": "ms",
                        "vs_baseline": None}),
        ])}
        path = tmp_path / "BENCH_r07.json"
        path.write_text(json.dumps(dump))
        hist = pw.BenchHistory()
        assert hist.import_bench_files([str(path)]) == 2
        assert hist.import_bench_files([str(path)]) == 0  # idempotent
        (tps,) = hist.series("tps")
        assert tps["run"] == "r07" and tps["source"] == "BENCH_r07.json"
        assert tps["raw_value"] == 100.25 and tps["git_sha"] == "import"
        assert tps["extra"] == {"mfu": 0.4}

    def test_importer_reads_this_repos_real_dumps(self):
        hist = pw.BenchHistory()
        added = hist.import_bench_files(root=DUMPS)
        # tests/data holds two hand-written dumps in the driver's
        # {n, cmd, rc, tail, parsed} schema (the rounds' own dumps were
        # removed in PR 22) — every imported record passes the schema
        assert added >= 10
        assert "resnet50_train_imgs_per_sec_per_chip" in hist.metrics()
        for rec in hist:
            pw.validate_record(rec)


# ---------------------------------------------------------------------------
# the detector
# ---------------------------------------------------------------------------

class TestRegressionDetector:
    def test_flat_series_with_noise_stays_silent(self):
        det = pw.RegressionDetector()
        # +-0.5% deterministic jitter: inside the 2% noise floor
        noise = (0.004, -0.003, 0.005, -0.005, 0.002, -0.004)
        values = [100.0 * (1.0 + noise[i % len(noise)])
                  for i in range(24)]
        assert det.check_series(values, direction=1) == []
        assert det.check_series(values, direction=-1) == []

    def test_planted_step_fires_exactly_once(self):
        det = pw.RegressionDetector()
        values = [100.0] * 10 + [80.0] * 6  # 20% drop, level persists
        firings = det.check_series(values, direction=1)
        assert len(firings) == 1
        i, baseline, delta, thresh = firings[0]
        assert i == 10 and baseline == 100.0
        assert abs(delta + 0.20) < 1e-9 and delta < -thresh

    def test_direction_gates_what_counts_as_bad(self):
        det = pw.RegressionDetector()
        up = [100.0] * 6 + [120.0] * 3
        # a 20% jump is an improvement up-is-good, a regression
        # down-is-good — same series, opposite verdicts
        assert det.check_series(up, direction=1) == []
        assert len(det.check_series(up, direction=-1)) == 1
        assert len(det.check_series(up, two_sided=True)) == 1

    def test_learned_floor_beats_the_static_one_on_noisy_series(self):
        det = pw.RegressionDetector()
        # ~6% swings are this series' OWN noise: the MAD-learned
        # threshold must absorb a swing the 2% static floor would flag
        values = [100.0, 106.0, 94.0, 105.0, 95.0, 106.0, 94.0,
                  105.0, 95.0, 106.0]
        assert det.check_series(values, direction=1) == []

    def test_check_attaches_suspect_region(self):
        clean, planted = pw.selfcheck()
        assert clean == []
        assert planted, "planted 20% drop must fire"
        assert all(r.suspect_region == "gpt_attention" for r in planted)
        assert all(r.suspect_delta_ms > 0 for r in planted)
        msg = planted[0].message()
        assert "gpt_fast_tokens_per_sec" in msg
        assert "-20" in msg and "gpt_attention" in msg

    def test_unit_direction_table_pinned(self):
        assert pw.unit_direction("imgs/sec") == 1
        assert pw.unit_direction("tokens/sec") == 1
        assert pw.unit_direction("percent") == 1
        assert pw.unit_direction("ms") == -1
        assert pw.unit_direction("bytes") == -1
        assert pw.unit_direction("skipped") == 0
        assert pw.unit_direction("error") == 0
        # suffix inference covers spellings the table never listed
        assert pw.unit_direction("reqs/sec") == 1
        assert pw.unit_direction("step_ms") == -1
        assert pw.unit_direction("furlongs") == 0

    def test_every_bench_emitted_unit_has_a_direction(self):
        """The direction table is pinned against bench.py's ACTUAL
        emitted units: every literal unit passed to ``_emit`` must be
        direction-carrying (or one of the two non-series markers), so a
        new bench line can never silently fall out of the detector."""
        with open(os.path.join(REPO, "bench.py")) as f:
            tree = ast.parse(f.read())
        units = set()
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "_emit"
                    and len(node.args) >= 3
                    and isinstance(node.args[2], ast.Constant)):
                units.add(node.args[2].value)
        assert {"imgs/sec", "tokens/sec", "ms"} <= units  # scan works
        for unit in units:
            if unit in ("skipped", "error"):
                continue
            assert pw.unit_direction(unit) != 0, unit


# ---------------------------------------------------------------------------
# attribution diffs
# ---------------------------------------------------------------------------

class TestAttributionDiff:
    def test_suspect_is_the_region_that_grew_most(self):
        before = [{"region": "embed", "modeled_ms": 0.5},
                  {"region": "attn", "modeled_ms": 3.0},
                  {"region": "mlp", "modeled_ms": 2.0}]
        after = [{"region": "embed", "modeled_ms": 0.5},
                 {"region": "attn", "modeled_ms": 4.2},
                 {"region": "mlp", "modeled_ms": 1.9}]
        diff = pw.AttributionDiff(before, after)
        worst = diff.suspect()
        assert worst.region == "attn" and worst.basis == "modeled"
        assert abs(worst.delta_ms - 1.2) < 1e-9
        assert "attn" in diff.markdown()

    def test_measured_preferred_over_modeled(self):
        before = [{"region": "attn", "modeled_ms": 3.0,
                   "measured_ms": 3.5}]
        after = [{"region": "attn", "modeled_ms": 3.0,
                  "measured_ms": 4.5}]
        (delta,) = pw.AttributionDiff(before, after).regions
        assert delta.basis == "measured" and delta.delta_ms == 1.0

    def test_nothing_grew_means_no_suspect(self):
        rep = [{"region": "attn", "modeled_ms": 3.0}]
        assert pw.AttributionDiff(rep, rep).suspect() is None


# ---------------------------------------------------------------------------
# cost-model drift
# ---------------------------------------------------------------------------

def _drift_history(ratios, metric="step_ms"):
    hist = pw.BenchHistory()
    for i, ratio in enumerate(ratios):
        hist.record(metric, 5.0 * ratio, "ms", run=f"r{i:02d}",
                    git_sha="s", host="h",
                    extras={"modeled_step_ms": 5.0,
                            "step_time_ms": 5.0 * ratio})
    return hist


class TestModelDrift:
    def test_series_is_measured_over_modeled(self):
        hist = _drift_history([1.30, 1.31, 1.29])
        (pts,) = pw.drift_series(hist).values()
        assert [round(r, 2) for _, _, r in pts] == [1.30, 1.31, 1.29]

    def test_stable_gap_is_not_an_alert(self):
        # a constant 30% model gap is a LEVEL, not a shift
        hist = _drift_history([1.30] * 8)
        assert pw.detect_drift_shifts(hist) == []

    def test_shift_alerts_both_directions(self):
        worse = _drift_history([1.30] * 6 + [1.60] * 2)
        (shift,) = pw.detect_drift_shifts(worse)
        assert shift.ratio == 1.60 and shift.delta_frac > 0
        assert "model-drift" in shift.message()
        better = _drift_history([1.30] * 6 + [1.05] * 2)
        (shift,) = pw.detect_drift_shifts(better)
        assert shift.delta_frac < 0  # improvements alert too

    def test_publish_drift_gauges(self):
        hist = _drift_history([1.30, 1.40], metric="a")
        for i, ratio in enumerate([0.50, 0.60]):
            hist.record("b", 5.0 * ratio, "ms", run=f"r{i:02d}",
                        git_sha="s", host="h",
                        extras={"modeled_step_ms": 5.0,
                                "step_time_ms": 5.0 * ratio})
        reg = MetricsRegistry()
        latest = pw.publish_drift(hist, reg)
        assert latest == {"a": 1.40, "b": 0.60}
        snap = reg.snapshot()
        assert snap["perf/model_drift/a"] == 1.40
        assert snap["perf/model_drift/b"] == 0.60
        # the scalar is the worst |log ratio|: 0.60 beats 1.40
        assert snap["perf/model_drift"] == 0.60


# ---------------------------------------------------------------------------
# the CLI contract (jax-free, so subprocess is cheap)
# ---------------------------------------------------------------------------

def _run_cli(*argv):
    return subprocess.run(
        [sys.executable, "-m", "apex_tpu.perfwatch"] + list(argv),
        capture_output=True, text=True, cwd=REPO)


class TestCLI:
    def _write_history(self, tmp_path, planted):
        path = str(tmp_path / "h.jsonl")
        disk = pw.BenchHistory(path)
        for rec in pw.synthetic_history(planted=planted):
            disk.append(rec)
        return path

    def test_check_clean_exits_zero(self, tmp_path):
        path = self._write_history(tmp_path, planted=False)
        proc = _run_cli("--check", "--history", path)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "-> clean" in proc.stdout

    def test_check_planted_exits_one_naming_the_region(self, tmp_path):
        path = self._write_history(tmp_path, planted=True)
        proc = _run_cli("--check", "--history", path)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "gpt_fast_tokens_per_sec" in proc.stdout
        assert "-20" in proc.stdout          # the delta
        assert "gpt_attention" in proc.stdout  # the suspect region

    def test_selfcheck_exit_codes(self):
        proc = _run_cli("--selfcheck")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "selfcheck ok" in proc.stdout

    def test_report_renders_markdown(self, tmp_path):
        path = self._write_history(tmp_path, planted=True)
        proc = _run_cli("--report", "-", "--history", path)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "# Performance observatory" in proc.stdout
        assert "gpt_attention" in proc.stdout

    def test_bootstrap_ingests_the_checked_in_rounds(self):
        # no --history: the CLI bootstraps in-memory from the root's
        # BENCH_r*.json dumps — the acceptance path
        proc = _run_cli("--check", "--root", DUMPS)
        assert proc.returncode == 0, proc.stdout + proc.stderr


# ---------------------------------------------------------------------------
# the bench.py round trip: satellite 1's precision guarantee
# ---------------------------------------------------------------------------

class TestBenchRoundTrip:
    def test_sub_display_precision_delta_survives(self, tmp_path,
                                                  monkeypatch, capsys):
        """0.1000 vs 0.1003 both PRINT as 0.1 — the 2-decimal display
        quantization that forced gpt_decode_goodput into percent. The
        history's raw_value must keep the 0.3% delta alive for the
        detector."""
        import bench
        path = str(tmp_path / "h.jsonl")
        monkeypatch.setenv("APEX_BENCH_HISTORY", path)
        monkeypatch.setattr(bench, "_HISTORY", None)
        monkeypatch.setattr(bench, "_RESULTS", [])
        bench._emit("rt_ms", 0.1000, "ms", None)
        bench._emit("rt_ms", 0.1003, "ms", None)
        printed = [json.loads(line)
                   for line in capsys.readouterr().out.splitlines()]
        assert [p["value"] for p in printed] == [0.1, 0.1]  # quantized
        back = pw.BenchHistory(path)
        raw = [r["raw_value"] for r in back.series("rt_ms")]
        assert raw == [0.1000, 0.1003]
        assert abs(raw[1] / raw[0] - 1.003) < 1e-9

    def test_emit_keeps_attribution_out_of_printed_lines(
            self, tmp_path, monkeypatch, capsys):
        import bench
        path = str(tmp_path / "h.jsonl")
        monkeypatch.setenv("APEX_BENCH_HISTORY", path)
        monkeypatch.setattr(bench, "_HISTORY", None)
        monkeypatch.setattr(bench, "_RESULTS", [])
        bench._emit("rt2_ms", 5.2, "ms", None,
                    modeled_step_ms=5.0, step_time_ms=5.2,
                    attribution=[{"region": "attn", "modeled_ms": 3.0}])
        (line,) = [json.loads(x)
                   for x in capsys.readouterr().out.splitlines()]
        # printed line keeps its pre-observatory shape
        assert "attribution" not in line and "step_time_ms" not in line
        assert line["modeled_step_ms"] == 5.0
        (rec,) = pw.BenchHistory(path).series("rt2_ms")
        # ... while the history record carries the full breakdown
        assert rec["attribution"] == [{"region": "attn",
                                       "modeled_ms": 3.0}]
        assert rec["step_time_ms"] == 5.2
        # and the drift series sees the pair immediately
        (pts,) = pw.drift_series(pw.BenchHistory(path)).values()
        assert abs(pts[0][2] - 5.2 / 5.0) < 1e-9

    def test_disabled_history_is_a_no_op(self, monkeypatch, capsys):
        import bench
        monkeypatch.setenv("APEX_BENCH_HISTORY", "off")
        monkeypatch.setattr(bench, "_HISTORY", None)
        monkeypatch.setattr(bench, "_RESULTS", [])
        bench._emit("rt3_ms", 1.0, "ms", None)
        assert bench._history() is None
        assert json.loads(capsys.readouterr().out)["value"] == 1.0

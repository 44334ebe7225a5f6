"""The unified static-analysis engine (``apex_tpu.analysis``).

One consolidated suite replacing the six per-script test classes that
used to live in ``test_observability.py`` (PR 11):

- **Family B (ast)** — every rule passes on the real tree, and a
  parametrized planted-violation table proves each rule still fires on
  exactly its own violation (same rigor as the old per-script classes,
  one harness).
- **Family A (jaxpr)** — planted-violation fixtures for every program
  rule: one shard_map grad-sync program parameterized by WHICH historical
  bug is planted (flat-gradient barrier, smuggled raw collective,
  missing shared-grad psum) runs the full ``lint_program`` surface and
  must fire exactly its own rule (cross-talk check); donation and
  recompile fixtures cover the other two rules.
- **CLI** — ``python -m apex_tpu.analysis --all`` is green on the clean
  tree (tier-1's consolidated entry point) and red on a planted one.
"""

import contextlib
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.analysis import iter_rules
from apex_tpu.analysis.astlint import repo_root
from apex_tpu.analysis.core import AnalysisError
from apex_tpu.analysis.program import (check_donation,
                                       check_shared_grad_reduction,
                                       lint_program, recompile_guard)
from apex_tpu.analysis.rules_ast import (ANNOTATIONS, METRIC_PREFIXES,
                                         rule_annotations,
                                         rule_bench_configs,
                                         rule_bench_history,
                                         rule_collectives,
                                         rule_elastic_exits,
                                         rule_metric_families,
                                         rule_metrics_doc,
                                         rule_remat_names)
from jax import shard_map

REPO = repo_root()


# ---------------------------------------------------------------------------
# Family B: clean tree
# ---------------------------------------------------------------------------

AST_RULES = {r.name: r for r in iter_rules("ast")}


@pytest.mark.parametrize("name", sorted(AST_RULES))
def test_ast_rule_clean_on_this_tree(name):
    findings, notes = AST_RULES[name].run(REPO)
    assert not findings, "\n".join(str(f) for f in findings)
    assert notes  # every rule reports what it checked


def test_annotation_contract_size():
    """The table doubles as the pyprof region vocabulary: 20 contract
    entries as of PR 20 (4 original + bucketed allreduce + optimizer_step
    + 8 model phases + 2 tp layers + 4 serving regions incl.
    serve_verify)."""
    _, notes = rule_annotations(REPO)
    assert len(notes) == len(ANNOTATIONS) == 20


# ---------------------------------------------------------------------------
# Family B: planted violations (one parametrized table)
# ---------------------------------------------------------------------------

def _write(tmp_path, rel, text):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def _seed_bench_repo(tmp_path, bench_src):
    _write(tmp_path, "apex_tpu/config.py",
           "import dataclasses\n"
           "@dataclasses.dataclass(frozen=True)\n"
           "class ModelConfig:\n"
           "    name: str = 'gpt'\n"
           "    remat_policy: str = None\n"
           "@dataclasses.dataclass(frozen=True)\n"
           "class ParallelConfig:\n"
           "    tensor_model_parallel_size: int = 1\n"
           "@dataclasses.dataclass(frozen=True)\n"
           "class BatchConfig:\n"
           "    global_batch_size: int = 64\n"
           "@dataclasses.dataclass(frozen=True)\n"
           "class OptimizerConfig:\n"
           "    name: str = 'adam'\n"
           "    zero: int = 0\n"
           "@dataclasses.dataclass(frozen=True)\n"
           "class TrainConfig:\n"
           "    model: ModelConfig = ModelConfig()\n"
           "    parallel: ParallelConfig = ParallelConfig()\n"
           "    batch: BatchConfig = BatchConfig()\n"
           "    optimizer: OptimizerConfig = OptimizerConfig()\n"
           "    ddp_bucket_bytes: int = None\n")
    _write(tmp_path, "apex_tpu/models/gpt.py",
           "import dataclasses\n"
           "@dataclasses.dataclass(frozen=True)\n"
           "class GPTConfig:\n"
           "    hidden_size: int = 768\n"
           "    remat_policy: str = None\n")
    _write(tmp_path, "bench.py", bench_src)


def _plant_annotations(tmp_path):
    (tmp_path / "apex_tpu").mkdir()  # empty tree: every annotation gone


def _expect_annotations(findings):
    assert len(findings) == len(ANNOTATIONS)
    assert all(f.kind == "MISSING" for f in findings)


def _plant_gather(tmp_path):
    _write(tmp_path, "apex_tpu/transformer/bad.py",
           "import jax\n"
           "def f(x):\n"
           "    return jax.lax.all_gather(x, 'tensor', axis=0)\n")


def _expect_gather(findings):
    assert any("bad.py:3" in f.where and "all_gather" in f.message
               for f in findings)


def _plant_scatter(tmp_path):
    _write(tmp_path, "apex_tpu/transformer/bad.py",
           "import jax\n"
           "def sync(g):\n"
           "    return jax.lax.psum_scatter(g, 'data', tiled=True)\n")


def _expect_scatter(findings):
    assert any("bad.py:3" in f.where and "reduce_scatter_grads"
               in f.message for f in findings)


def _plant_grad_psum(tmp_path):
    src = ("import jax\n"
           "def sync(g):\n"
           "    return jax.lax.psum(g, 'data')\n")
    _write(tmp_path, "apex_tpu/optimizers/bad.py", src)
    # the same line OUTSIDE a grad-sync module is legitimate
    _write(tmp_path, "apex_tpu/normalization/fine.py", src)


def _expect_grad_psum(findings):
    assert any("bad.py:3" in f.where and "grad-sync" in f.message
               for f in findings)
    assert not any("fine.py" in f.where for f in findings)


def _plant_metrics_doc(tmp_path):
    _write(tmp_path, "apex_tpu/m.py",
           "from apex_tpu.observability import ingraph\n"
           "def f(x, name, registry, reg, buckets):\n"
           "    ingraph.record('health/rogue_metric', x)\n"
           "    ingraph.record(f'health/{name}/rogue_family', x)\n"
           "    registry.gauge('perf/rogue_attribution').set(x)\n"
           "    reg.counter('ckpt/rogue_bytes').inc(x)\n"
           "    reg.histogram('serve/rogue_ms').observe(x)\n"
           # the PR 12 call shapes: a bucketed latency histogram and an
           # slo/ gauge — the doc contract must see through both
           "    reg.histogram('serve/rogue_wait_ms', buckets).observe(x)\n"
           "    reg.gauge('slo/rogue_goodput').set(x)\n"
           # the PR 13 supervisor family: elastic/* is under the doc
           # contract like every other elastic-runtime family
           "    reg.gauge('elastic/rogue_world').set(x)\n"
           # the PR 14 fleet merge layer: fleet/* (supervisor straggler
           # gauges) and train/* (rank-side step counters) join the
           # contract
           "    reg.gauge('fleet/rogue_skew').set(x)\n"
           "    reg.counter('train/rogue_steps').inc(x)\n"
           # the PR 15 resilience call shapes: reason-keyed retirement
           # counters and the brownout gauge — an undocumented
           # rejection/expiry/poison counter must fire like any other
           "    reg.counter('serve/rogue_rejected').inc()\n"
           "    reg.counter('serve/rogue_poisoned').inc()\n"
           "    reg.gauge('serve/rogue_brownout').set(x)\n"
           # the PR 18 perfwatch call shapes: a scalar drift gauge and a
           # per-metric f-string drift family — the observatory's
           # published names are under the contract like any other perf/
           "    reg.gauge('perf/rogue_drift').set(x)\n"
           "    reg.gauge(f'perf/rogue_drift/{name}').set(x)\n")
    _write(tmp_path, "docs/OBSERVABILITY.md", "| nothing documented |\n")


def _expect_metrics_doc(findings):
    undoc = [f for f in findings if f.kind == "UNDOC"]
    # record x2 + gauge x7 + counter x4 + hist x2
    assert len(undoc) == 15
    for name in ("health/rogue_metric", "health/<>/rogue_family",
                 "perf/rogue_attribution", "ckpt/rogue_bytes",
                 "serve/rogue_ms", "serve/rogue_wait_ms",
                 "slo/rogue_goodput", "elastic/rogue_world",
                 "fleet/rogue_skew", "train/rogue_steps",
                 "serve/rogue_rejected", "serve/rogue_poisoned",
                 "serve/rogue_brownout", "perf/rogue_drift",
                 "perf/rogue_drift/<>"):
        assert any(name in f.message for f in undoc), name


def _plant_metric_family(tmp_path):
    _write(tmp_path, "apex_tpu/m.py",
           "def f(reg, x, i):\n"
           "    reg.counter('newfam/widgets').inc()\n"
           "    reg.counter('jax/compiles').inc()\n"          # exempt
           "    reg.gauge(f'memory/peak/device{i}').set(x)\n"  # exempt
           "    reg.gauge('serve/queue_depth').set(x)\n"       # known
           "    reg.gauge('slo/goodput').set(x)\n"             # known (PR 12)
           "    reg.gauge('elastic/world_size').set(x)\n"      # known (PR 13)
           "    reg.gauge('fleet/step_skew').set(x)\n"         # known (PR 14)
           "    reg.counter('train/steps').inc()\n"            # known (PR 14)
           "    reg.counter('serve/rejected').inc()\n"         # known (PR 15)
           "    reg.counter('serve/poisoned').inc()\n"         # known (PR 15)
           "    reg.gauge('serve/brownout').set(x)\n"          # known (PR 15)
           "    reg.gauge('no_slash_name').set(x)\n")          # unprefixed
    # even a documented row does not excuse an unregistered FAMILY
    _write(tmp_path, "docs/OBSERVABILITY.md", "| `newfam/widgets` |\n")


def _expect_metric_family(findings):
    assert len(findings) == 1
    f = findings[0]
    assert f.kind == "ROGUE" and "m.py:2" in f.where
    assert "newfam/" in f.message and "METRIC_PREFIXES" in f.message


def _plant_remat(tmp_path):
    _write(tmp_path, "apex_tpu/remat.py",
           "CHECKPOINT_NAMES = ('qkv_out', 'ln_out')\n"
           "SELECTIVE_SAVE = ('qkv_out', 'phantom',)\n")
    _write(tmp_path, "apex_tpu/bad.py",
           "from jax.ad_checkpoint import checkpoint_name\n"
           "def f(self, x):\n"
           "    x = checkpoint_name(x, 'rogue_act')\n"
           "    x = self._tag(x, 'another_rogue')\n"
           "    return self._tag(x, 'qkv_out')\n")


def _expect_remat(findings):
    orphans = [f for f in findings if f.kind == "ORPHAN"]
    assert any("rogue_act" in f.message and "bad.py:3" in f.where
               for f in orphans)
    assert any("another_rogue" in f.message and "bad.py:4" in f.where
               for f in orphans)
    assert any("phantom" in f.message and "SELECTIVE_SAVE" in f.where
               for f in orphans)
    assert not any("qkv_out" in f.message for f in orphans)


def _elastic_chokepoint(tmp_path):
    _write(tmp_path, "apex_tpu/utils/autoresume.py",
           "import sys\n"
           "class AutoResume:\n"
           "    def request_resume(self, exit_code=0):\n"
           "        sys.exit(exit_code)\n")
    (tmp_path / "apex_tpu" / "elastic").mkdir(parents=True,
                                              exist_ok=True)


def _plant_elastic_exits(tmp_path):
    _elastic_chokepoint(tmp_path)
    _write(tmp_path, "apex_tpu/elastic/bad.py",
           "import os, sys\n"
           "def f(code):\n"
           "    sys.exit(code)\n"
           "    os._exit(code)\n"
           "    exit(code)\n"
           "    raise SystemExit(code)\n")


def _expect_elastic_exits(findings):
    flagged = [f for f in findings if f.kind == "EXIT"]
    assert len(flagged) == 4
    for spelling, lineno in (("sys.exit", 3), ("os._exit", 4),
                             ("exit", 5), ("raise SystemExit", 6)):
        assert any(spelling in f.message and f"bad.py:{lineno}"
                   in f.where for f in flagged), spelling


def _plant_elastic_choke_rot(tmp_path):
    _elastic_chokepoint(tmp_path)
    _write(tmp_path, "apex_tpu/utils/autoresume.py",
           "class AutoResume:\n"
           "    def request_resume(self, exit_code=0):\n"
           "        pass\n")


def _expect_elastic_choke_rot(findings):
    assert any(f.kind == "CHOKE" for f in findings)


_LAUNCH_CHOKE = ("def _supervisor_exit(code):\n"
                 "    import sys\n"
                 "    sys.exit(int(code))\n")


def _plant_launch_exit(tmp_path):
    """launch.py may exit ONLY inside _supervisor_exit: a sys.exit in
    any other supervisor function is the violation; the blessed one is
    not."""
    _elastic_chokepoint(tmp_path)
    _write(tmp_path, "apex_tpu/elastic/launch.py",
           "import sys\n"
           + _LAUNCH_CHOKE +
           "def run(report):\n"
           "    sys.exit(0 if report else 1)\n")


def _expect_launch_exit(findings):
    flagged = [f for f in findings if f.kind == "EXIT"]
    assert len(flagged) == 1
    assert "launch.py:6" in flagged[0].where
    assert "_supervisor_exit" in flagged[0].message
    # the blessed chokepoint itself never fires, and its shape is fine
    assert not any(f.kind == "CHOKE" for f in findings)


def _plant_launch_choke_rot(tmp_path):
    """Chokepoint rot: _supervisor_exit exists but no longer holds
    exactly one sys.exit (here: two) — the anchor the rule pins must not
    silently decay."""
    _elastic_chokepoint(tmp_path)
    _write(tmp_path, "apex_tpu/elastic/launch.py",
           "import sys\n"
           "def _supervisor_exit(code):\n"
           "    sys.exit(int(code))\n"
           "    sys.exit(1)\n")


def _expect_launch_choke_rot(findings):
    choke = [f for f in findings if f.kind == "CHOKE"
             and "launch.py" in f.where]
    assert len(choke) == 1 and "found 2" in choke[0].message


def _plant_bench(tmp_path):
    _seed_bench_repo(
        tmp_path,
        "BENCH_TRAIN_CONFIGS = {\n"
        "  'leg': {'model': {'remat_policy': 'selective',\n"
        "                    'remat_mode': 'full'},\n"
        "          'bucket_bytes': 4096,\n"
        "          'optimizer': {'zero': 1}},\n"
        "}\n"
        # stated-SLO contract: one bad metric name, one bad quantile,
        # one bad threshold, one fully valid triple
        "DECODE_SLO = (('latency_ms', 95.0, 2000.0),\n"
        "              ('ttft_ms', 101.0, 500.0),\n"
        "              ('tpot_ms', 99.0, 0.0),\n"
        "              ('e2e_ms', 99.0, 4000.0))\n"
        "def _gpt_train_step(batch=8, seq=1024, **cfg_overrides):\n"
        "    pass\n"
        "def bench_ok():\n"
        "    _gpt_train_step(batch=8, hidden_size=768)\n"
        "def bench_bad():\n"
        "    _gpt_train_step(hidden_dims=768)\n")
    _write(tmp_path, "BENCH_CONFIGS.json",
           '[{"metric": "m", "config": {"ddp_bucket_bytes": 1,'
           ' "optimizer": {"zero_stage": 1}}}]')


def _expect_bench(findings):
    unknown = [f for f in findings if f.kind == "UNKNOWN"]
    assert any("model.'remat_mode'" in f.message for f in unknown)
    assert any("'bucket_bytes'" in f.message for f in unknown)
    assert any("optimizer.'zero_stage'" in f.message
               and "BENCH_CONFIGS.json" in f.where for f in unknown)
    assert any("hidden_dims" in f.message for f in unknown)
    # the stated-SLO contract (PR 12): bad metric/quantile/threshold fire
    slo = [f for f in unknown if "DECODE_SLO" in f.where]
    assert any("'latency_ms'" in f.message for f in slo)
    assert any("101.0" in f.message for f in slo)
    assert any("threshold_ms" in f.message for f in slo)
    assert not any("e2e_ms" in f.where for f in slo)  # the valid triple
    # valid keys in the same legs are NOT flagged
    assert not any("remat_policy" in f.message for f in unknown)
    assert not any("'zero'" in f.message for f in unknown)


def _plant_bench_history(tmp_path):
    """A perfwatch-era schema fork: the writer renamed ``value`` to
    ``display_value`` and grew a ``hostname`` promotion the table never
    learned about, while an on-disk history still carries both old- and
    new-world records."""
    _write(tmp_path, "apex_tpu/observability/perfwatch.py",
           "HISTORY_FIELDS = (\n"
           "    ('metric', 'required'),\n"
           "    ('value', 'required'),\n"
           "    ('raw_value', 'required'),\n"
           "    ('unit', 'required'),\n"
           "    ('config', 'optional'),\n"
           ")\n"
           "def make_record(metric, value, unit):\n"
           "    rec = {\n"
           "        'metric': metric,\n"
           "        'display_value': round(value, 2),\n"
           "        'raw_value': value,\n"
           "        'unit': unit,\n"
           "    }\n"
           "    rec['hostname'] = 'n1'\n"
           "    return rec\n")
    _write(tmp_path, "BENCH_HISTORY.jsonl",
           '{"metric": "m", "value": 1.0, "raw_value": 1.0,'
           ' "unit": "ms", "rogue_key": 1}\n'
           '{"metric": "m"}\n')


def _expect_bench_history(findings):
    writer = [f for f in findings if "make_record" in f.where]
    # the renamed required key fires both ways: absent + rogue
    assert any(f.kind == "MISSING" and "'value'" in f.message
               for f in writer)
    assert any(f.kind == "ROGUE" and "'display_value'" in f.message
               for f in writer)
    # the un-tabled promotion
    assert any(f.kind == "ROGUE" and "'hostname'" in f.message
               for f in writer)
    disk = [f for f in findings if "BENCH_HISTORY.jsonl" in f.where]
    assert any(f.kind == "UNKNOWN" and "'rogue_key'" in f.message
               and ":1" in f.where for f in disk)
    missing2 = [f for f in disk if f.kind == "MISSING" and ":2" in f.where]
    assert {m.split("'")[1] for m in (f.message for f in missing2)} == \
        {"value", "raw_value", "unit"}
    # keys the table DOES know are not flagged
    assert not any("'config'" in f.message for f in findings)
    assert not any("'raw_value'" in f.message and f.kind != "MISSING"
                   for f in findings)


def test_slo_metric_mirror_pinned():
    """rules_ast.SLO_METRICS is a jax-free mirror of the slo module's
    latency vocabulary — they must never drift."""
    from apex_tpu.analysis.rules_ast import SLO_METRICS
    from apex_tpu.observability.slo import LATENCY_METRICS
    assert SLO_METRICS == LATENCY_METRICS


PLANTED = [
    ("ast-annotations", rule_annotations, _plant_annotations,
     _expect_annotations),
    ("ast-collectives/gather", rule_collectives, _plant_gather,
     _expect_gather),
    ("ast-collectives/scatter", rule_collectives, _plant_scatter,
     _expect_scatter),
    ("ast-collectives/grad-psum", rule_collectives, _plant_grad_psum,
     _expect_grad_psum),
    ("ast-metrics-doc", rule_metrics_doc, _plant_metrics_doc,
     _expect_metrics_doc),
    ("ast-metric-families", rule_metric_families, _plant_metric_family,
     _expect_metric_family),
    ("ast-remat-names", rule_remat_names, _plant_remat, _expect_remat),
    ("ast-elastic-exits", rule_elastic_exits, _plant_elastic_exits,
     _expect_elastic_exits),
    ("ast-elastic-exits/choke-rot", rule_elastic_exits,
     _plant_elastic_choke_rot, _expect_elastic_choke_rot),
    ("ast-elastic-exits/launch", rule_elastic_exits, _plant_launch_exit,
     _expect_launch_exit),
    ("ast-elastic-exits/launch-choke-rot", rule_elastic_exits,
     _plant_launch_choke_rot, _expect_launch_choke_rot),
    ("ast-bench-configs", rule_bench_configs, _plant_bench,
     _expect_bench),
    ("ast-bench-history", rule_bench_history, _plant_bench_history,
     _expect_bench_history),
]


@pytest.mark.parametrize("case", PLANTED, ids=[c[0] for c in PLANTED])
def test_ast_planted_violation_fires(case, tmp_path):
    _name, rule_fn, plant, expect = case
    plant(tmp_path)
    findings, _notes = rule_fn(str(tmp_path))
    assert findings
    expect(findings)


def test_missing_inputs_fail_loudly(tmp_path):
    """A tree missing the contract anchors is a failure, not a pass."""
    (tmp_path / "apex_tpu").mkdir()
    for rule_fn in (rule_metrics_doc, rule_remat_names,
                    rule_elastic_exits, rule_bench_configs,
                    rule_bench_history):
        findings, _ = rule_fn(str(tmp_path))
        assert any(f.kind == "MISSING" for f in findings), rule_fn


def test_documenting_fixes_metrics_doc(tmp_path):
    """The doc-side fix path: adding rows (any placeholder spelling)
    silences the rule."""
    _plant_metrics_doc(tmp_path)
    _write(tmp_path, "docs/OBSERVABILITY.md",
           "| `health/rogue_metric` | `health/<tree>/rogue_family` |\n"
           "| `perf/rogue_attribution` | `ckpt/rogue_bytes` |\n"
           "| `serve/rogue_ms` | `serve/rogue_wait_ms` |\n"
           "| `slo/rogue_goodput` | `elastic/rogue_world` |\n"
           "| `fleet/rogue_skew` | `train/rogue_steps` |\n"
           "| `serve/rogue_rejected` | `serve/rogue_poisoned` |\n"
           "| `serve/rogue_brownout` | `perf/rogue_drift` |\n"
           "| `perf/rogue_drift/<metric>` |\n")
    findings, _ = rule_metrics_doc(str(tmp_path))
    assert not findings


# ---------------------------------------------------------------------------
# the CLI (tier-1's consolidated entry point)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_all_green_on_clean_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "apex_tpu.analysis", "--all"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "selfcheck ok" in proc.stdout  # jaxpr rules proved both ways


def test_cli_single_rule_json_and_planted_repo(tmp_path):
    _plant_gather(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "apex_tpu.analysis", "--rule",
         "ast-collectives", "--json", "--repo", str(tmp_path)],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["ok"] is False
    (entry,) = payload["rules"]
    assert entry["rule"] == "ast-collectives"
    assert any("bad.py:3" in f["where"] for f in entry["findings"])


# ---------------------------------------------------------------------------
# Family A: one grad-sync fixture program, one planted bug at a time
# ---------------------------------------------------------------------------

_N1, _N2, _NS = 24, 40, 4
_PADDED = _N1 + _N2


def _grad_sync_program(violation):
    """A miniature hybrid-trainer step on a 2x2 ``data x pipe`` mesh:
    grads of two 'local' params bucket-reduce-scatter over data inside
    the optimizer_step scope, the 'shared' param's grad psums over pipe.
    ``violation`` plants exactly one historical bug:

    - ``"collective"``: the scatters run through a helper OUTSIDE any
      blessed scope (the smuggled-raw-collective class);
    - ``"flat"``: the grads concatenate into the full padded flat vector
      before syncing (the PR 8 barrier class);
    - ``"shared"``: the shared grad is returned as the per-rank partial
      (the PR 7 drift class);
    - ``"none"``: the clean program.
    """
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("data", "pipe"))

    def f(w, b, s, x):
        def loss_fn(w, b, s):
            return (jnp.sum((x[:_N1] * w) ** 2)
                    + jnp.sum((x[:_N2] * b) ** 2)
                    + jnp.sum(x[:_NS] * s))
        gw, gb, gs = jax.grad(loss_fn, argnums=(0, 1, 2))(w, b, s)
        scope = (contextlib.nullcontext() if violation == "collective"
                 else jax.named_scope("optimizer_step"))

        def sync(g):  # the indirection an AST scan cannot see through
            return jax.lax.psum_scatter(g, "data", tiled=True)

        with scope:
            if violation == "flat":
                parts = (sync(jnp.concatenate([gw, gb])),)
            else:
                parts = (sync(gw), sync(gb))
        if violation != "shared":
            gs = jax.lax.psum(gs, "pipe")
        return (gs, *parts)

    out_specs = (P(), *([P("data")] * (1 if violation == "flat" else 2)))
    wrapped = shard_map(
        f, mesh=mesh, in_specs=(P(), P(), P(), P()), out_specs=out_specs)
    args = (jnp.arange(_N1, dtype=jnp.float32),
            jnp.arange(_N2, dtype=jnp.float32),
            jnp.arange(_NS, dtype=jnp.float32),
            jnp.ones(64, jnp.float32))
    return jax.make_jaxpr(wrapped)(*args).jaxpr


def _lint_fixture(jaxpr):
    return lint_program(
        jaxpr, collective_axes=("data",), flat_sizes=(_PADDED,),
        shared_outputs=[(0, "shared grad")], shared_axis="pipe",
        label="fixture")


@pytest.mark.parametrize("violation,expected_rule", [
    ("none", None),
    ("collective", "jaxpr-collectives"),
    ("flat", "jaxpr-flat-grad"),
    ("shared", "jaxpr-shared-grad"),
])
def test_jaxpr_fixture_fires_exactly_its_rule(violation, expected_rule):
    """The cross-talk contract: each planted bug fires its own rule and
    ONLY its own rule; the clean program is silent under the full lint."""
    findings = _lint_fixture(_grad_sync_program(violation))
    fired = {f.rule for f in findings}
    assert fired == (set() if expected_rule is None else {expected_rule}
                     ), findings


def test_jaxpr_collective_finding_names_scope_and_axis():
    findings = _lint_fixture(_grad_sync_program("collective"))
    assert len(findings) == 2  # one per smuggled scatter
    for f in findings:
        # lax.psum_scatter traces as psum_scatter or reduce_scatter
        # depending on the jax line
        assert "scatter" in f.message and "data" in f.message
        assert "optimizer_step" in f.message  # tells you where it belongs


def test_jaxpr_flat_finding_names_the_barrier_primitive():
    (finding,) = _lint_fixture(_grad_sync_program("flat"))
    assert "concatenate" in finding.message
    assert str(_PADDED) in finding.message


def test_jaxpr_shared_finding_points_at_the_fix():
    (finding,) = _lint_fixture(_grad_sync_program("shared"))
    assert "pipe" in finding.message
    assert "_finalize_shared" in finding.message  # the PR 7 fix site


# ---------------------------------------------------------------------------
# Family A: donation (the PR 9 double-donated scale-plane class)
# ---------------------------------------------------------------------------

class TestDonation:
    def test_shared_kvcache_scale_plane_detected(self):
        """The literal PR 9 bug, rebuilt: an int8 pool whose k/v
        scale planes are the SAME buffer double-donates it."""
        import dataclasses
        from apex_tpu.serving.cache import PagedKVCache
        cache = PagedKVCache.create(1, 3, 2, 8, 4, dtype=jnp.int8)
        assert not check_donation(donated_args=cache)  # create() is safe
        broken = dataclasses.replace(cache, v_scale=cache.k_scale)
        findings = check_donation(donated_args=broken)
        assert [f.kind for f in findings] == ["DOUBLE"]
        assert "donated twice" in findings[0].message

    def test_unaliased_donation_detected(self):
        import warnings
        a, b = jnp.arange(4.0), jnp.arange(8.0)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            lowered = jax.jit(lambda x, dead: x + 1.0,
                              donate_argnums=(0, 1)).trace(a, b).lower()
        findings = check_donation(lowered, expected_donated=2)
        assert any(f.kind == "UNALIASED" for f in findings)

    def test_clean_donation_silent(self):
        a, b = jnp.arange(4.0), jnp.arange(8.0)
        lowered = jax.jit(lambda x, y: (x + 1.0, y * 2.0),
                          donate_argnums=(0, 1)).trace(a, b).lower()
        assert not check_donation(lowered, donated_args=(a, b),
                                  expected_donated=2)

    def test_compiled_hlo_alias_map_parsed(self):
        """The compiled-program path (HLO header map) counts entries —
        the surface the ServingEngine construction self-check and the
        trainer's verify_donation run on."""
        a, b = jnp.arange(4.0), jnp.arange(8.0)
        compiled = jax.jit(lambda x, y: (x + 1.0, y * 2.0),
                           donate_argnums=(0, 1)).trace(
                               a, b).lower().compile()
        assert not check_donation(compiled, expected_donated=2,
                                  min_alias_bytes=a.nbytes + b.nbytes)
        findings = check_donation(compiled, expected_donated=3)
        assert any(f.kind == "UNALIASED" for f in findings)

    def test_cache_deserialized_executable_trusts_the_alias_map(self):
        """An executable deserialized from the PERSISTENT compilation
        cache reports ``alias_size_in_bytes == 0`` while its HLO alias
        map is intact (reproduced live: fresh compile 4096, cache hit 0,
        identical map — this hard-failed the dryrun serving leg on every
        warm-cache retry). With a COMPLETE map the floor must not fire;
        a genuinely partial alias (0 < bytes < floor) still must."""

        class FakeAnalysis:
            def __init__(self, alias):
                self.alias_size_in_bytes = alias

        class FakeCompiled:
            def __init__(self, alias):
                self._alias = alias

            def as_text(self):
                return ("HloModule jit_step, "
                        "input_output_alias={ {0}: (0, {}, "
                        "may-alias), {1}: (1, {}, may-alias) }\n")

            def memory_analysis(self):
                return FakeAnalysis(self._alias)

        # cache case: 0 bytes next to a complete 2-entry map -> silent
        assert not check_donation(FakeCompiled(0), expected_donated=2,
                                  min_alias_bytes=4096)
        # partial alias: nonzero-but-small bytes -> still a finding
        findings = check_donation(FakeCompiled(100), expected_donated=2,
                                  min_alias_bytes=4096)
        assert [f.kind for f in findings] == ["UNALIASED"]
        assert "alias_size_in_bytes 100" in findings[0].message
        # 0 bytes next to an INCOMPLETE map is still two findings
        # (missing leaf + floor), not excused
        findings = check_donation(FakeCompiled(0), expected_donated=3,
                                  min_alias_bytes=4096)
        assert sorted(f.kind for f in findings) == ["UNALIASED",
                                                    "UNALIASED"]


# ---------------------------------------------------------------------------
# Family A: the zero-recompile budget
# ---------------------------------------------------------------------------

class TestRecompileGuard:
    def test_steady_shape_is_silent(self):
        step = jax.jit(lambda x: x * 3.0)
        step(jnp.ones(4))
        with recompile_guard("test") as g:
            for _ in range(3):
                step(jnp.ones(4))
        assert not g.findings

    def test_shape_leak_raises(self):
        step = jax.jit(lambda x: x * 3.0)
        with pytest.raises(AnalysisError, match="compile-storm"):
            with recompile_guard("test") as g:
                g.rebase()
                for n in (5, 6, 7):
                    step(jnp.ones(n))

    def test_rebase_forgives_warmup_only(self):
        step = jax.jit(lambda x: x * 3.0)
        with recompile_guard("test", raise_on_violation=False) as g:
            step(jnp.ones(9))   # warmup compile
            g.rebase()
            step(jnp.ones(9))   # cached: silent
        assert not g.findings
        (finding,) = _storm()
        assert finding.rule == "jaxpr-recompile"

    def test_loop_exception_not_masked(self):
        with pytest.raises(ZeroDivisionError):
            with recompile_guard("test"):
                raise ZeroDivisionError


def _storm():
    step = jax.jit(lambda x: x * 5.0)
    with recompile_guard("test", raise_on_violation=False) as g:
        step(jnp.ones(11))
        step(jnp.ones(12))
    return g.findings


# ---------------------------------------------------------------------------
# shared-grad rule: cone precision across wrappers
# ---------------------------------------------------------------------------

def test_shared_grad_cone_is_per_output():
    """The cone walk is per-output: a psum on ANOTHER output must not
    excuse the unreduced one (no rule-level cross-contamination)."""
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("pipe",))

    def f(a, b):
        return jax.lax.psum(a, "pipe"), b * 2.0  # b never reduced

    wrapped = shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                  out_specs=(P(), P()))
    jaxpr = jax.make_jaxpr(wrapped)(jnp.ones(4), jnp.ones(4)).jaxpr
    assert not check_shared_grad_reduction(jaxpr, [(0, "a")], "pipe")
    findings = check_shared_grad_reduction(jaxpr, [(1, "b")], "pipe")
    assert len(findings) == 1 and findings[0].kind == "PARTIAL"


# ---------------------------------------------------------------------------
# the port deleted the per-script boilerplate for good
# ---------------------------------------------------------------------------

def test_script_shims_carry_no_walker_boilerplate():
    """Each scripts/check_*.py is a thin shim over the engine: no private
    AST/file-walk copies may creep back in (they went from ~150 lines of
    duplicated walker each to <80-line shims in PR 11)."""
    import glob
    import os
    shims = sorted(glob.glob(os.path.join(REPO, "scripts", "check_*.py")))
    assert len(shims) == 6
    for path in shims:
        src = open(path).read()
        assert len(src.splitlines()) < 80, f"{path} grew boilerplate back"
        for needle in ("ast.walk", "os.walk", "ast.parse"):
            assert needle not in src, f"{path} re-inlined {needle}"
        assert "apex_tpu.analysis" in src  # it really is a shim

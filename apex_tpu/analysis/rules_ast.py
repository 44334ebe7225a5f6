"""Family-B rules: the repo's AST/text contract lints on one walker core.

Each rule here is the port of one historical ``scripts/check_*.py``
(those scripts remain as thin shims over this module), plus the
metric-family meta-lint that closes the "new family silently
undocumented" gap. Policy tables (allowlists, contract tables) live next
to their rule; the walk/report boilerplate lives once in
:mod:`apex_tpu.analysis.astlint`.

Rule -> encoded bug class (details + allowlisting in docs/ANALYSIS.md):

- ``ast-annotations`` — a refactor dropping a documented ``named_scope``
  silently rots the pyprof attribution-region vocabulary.
- ``ast-collectives`` — a raw ``lax.all_gather``/``psum_scatter``/grad
  ``psum`` bypasses the VMA shims / bucketing chokepoints.
- ``ast-metrics-doc`` — a ``record()``/``gauge()``/``counter()``/
  ``histogram()`` name in a checked family with no docs row.
- ``ast-metric-families`` — a metric name under a ``<prefix>/`` that is
  not a known family at all (the list in ``METRIC_PREFIXES`` used to be
  grown by hand per PR; this meta-lint makes forgetting it loud).
- ``ast-remat-names`` — a checkpoint-name tag literal outside the
  ``remat.CHECKPOINT_NAMES`` registry (no policy can save it).
- ``ast-elastic-exits`` — a process exit under ``apex_tpu/elastic/``
  outside the two blessed chokepoints: ``AutoResume.request_resume``
  (the runner's preemption exit) and ``launch.py::_supervisor_exit``
  (the supervisor CLI's exit-code propagation).
- ``ast-bench-configs`` — a bench-config key that no longer names a real
  config dataclass field (the leg silently falls back to defaults).
- ``ast-bench-history`` — the perfwatch JSONL schema keys drift from the
  writer's literal ``HISTORY_FIELDS`` table (a renamed key silently
  forks every future history file from every past one).
"""

from __future__ import annotations

import ast
import json
import os
import re
from typing import List, Tuple

from apex_tpu.analysis.core import Finding, Rule, register
from apex_tpu.analysis.astlint import (PACKAGE, callee_name,
                                       iter_package_trees, iter_py_files,
                                       literal_str, parse_file,
                                       tuple_literal)

__all__ = ["ANNOTATIONS", "ALLOWED_GATHER", "ALLOWED_SCATTER",
           "GRAD_SYNC_PREFIXES", "METRIC_PREFIXES", "EXEMPT_PREFIXES",
           "METRIC_CALLEES", "TAG_CALLEES", "REGISTRY_FILE", "ELASTIC_DIR",
           "CHOKEPOINT_FILE", "CHOKEPOINT_FUNC", "LAUNCH_FILE",
           "LAUNCH_CHOKEPOINT_FUNC", "CONFIG_CLASSES",
           "SECTIONS", "SLO_METRICS", "DOC", "PERFWATCH_FILE",
           "HISTORY_TABLE", "HISTORY_WRITER", "HISTORY_JSONL",
           "rule_annotations", "rule_collectives",
           "rule_metrics_doc", "rule_metric_families", "rule_remat_names",
           "rule_elastic_exits", "rule_bench_configs",
           "rule_bench_history"]

Findings = Tuple[List[Finding], List[str]]


def _p(*parts: str) -> str:
    return os.path.join(*parts)


# ---------------------------------------------------------------------------
# ast-annotations: the named_scope contract table
# ---------------------------------------------------------------------------

# annotation -> source files allowed to carry it (repo-relative). The
# contract is "exists in at least one of its owning files": moving an
# annotation to an unrelated module is a docs-breaking change and should
# fail here until the table (and docs) are updated. The table doubles as
# the pyprof attribution-region vocabulary: apex_tpu/pyprof/model.py's
# DEFAULT_REGIONS must stay a subset of these keys (asserted in
# tests/test_pyprof.py), so every region a step-time attribution report
# names is guaranteed to exist as a named_scope in source.
ANNOTATIONS = {
    "apex_ddp_allreduce": ["apex_tpu/parallel/distributed.py"],
    "apex_ddp_bucketed_allreduce": ["apex_tpu/parallel/distributed.py"],
    "sync_bn_stats": ["apex_tpu/parallel/sync_batchnorm.py"],
    "pipeline_tick": [
        "apex_tpu/transformer/pipeline_parallel/schedules.py"],
    "flash_attention": ["apex_tpu/ops/flash_attention.py"],
    "optimizer_step": ["apex_tpu/optimizers/_base.py"],
    # model phases (pyprof attribution regions)
    "gpt_embed": ["apex_tpu/models/gpt.py"],
    "gpt_ln": ["apex_tpu/models/gpt.py"],
    "gpt_attention": ["apex_tpu/models/gpt.py"],
    "gpt_mlp": ["apex_tpu/models/gpt.py"],
    "gpt_head_loss": ["apex_tpu/models/gpt.py"],
    "rn50_stem": ["apex_tpu/models/resnet.py"],
    "rn50_body": ["apex_tpu/models/resnet.py"],
    "rn50_head": ["apex_tpu/models/resnet.py"],
    # tensor-parallel layers (GEMM + dependent collective, tp > 1 only)
    "tp_column_linear": [
        "apex_tpu/transformer/tensor_parallel/layers.py"],
    "tp_row_linear": [
        "apex_tpu/transformer/tensor_parallel/layers.py"],
    # serving fast path: the decode kernel plus the AOT step bodies,
    # so pyprof attributes prefill vs decode vs speculative verify
    # (docs/SERVING.md)
    "decode_attention": ["apex_tpu/ops/flash_attention.py"],
    "serve_prefill": ["apex_tpu/serving/engine.py"],
    "serve_decode": ["apex_tpu/serving/engine.py"],
    "serve_verify": ["apex_tpu/serving/engine.py"],
}


def rule_annotations(repo: str) -> Findings:
    findings, notes = [], []
    for name, files in sorted(ANNOTATIONS.items()):
        needle = f'named_scope("{name}")'
        found_in = []
        for rel in files:
            try:
                with open(os.path.join(repo, rel)) as f:
                    if needle in f.read():
                        found_in.append(rel)
            except OSError:
                pass
        if found_in:
            notes.append(f"ok       {name}: {', '.join(found_in)}")
        else:
            findings.append(Finding(
                "ast-annotations", "MISSING", name,
                f"expected {needle} in {' or '.join(files)} — update the "
                f"source or the contract table (ANNOTATIONS in "
                f"apex_tpu/analysis/rules_ast.py + docs/OBSERVABILITY.md)"))
    return findings, notes


# ---------------------------------------------------------------------------
# ast-collectives: gathers/grad-syncs stay behind their chokepoints
# ---------------------------------------------------------------------------

# the only module allowed to touch lax.all_gather directly: the VMA shims
ALLOWED_GATHER = {
    _p("apex_tpu", "utils", "vma.py"),
}

# lax.psum_scatter: the grad-sync chokepoint (reduce_scatter_grads), plus
# the context-parallel sequence-dim scatter — an ACTIVATION collective
# (RowParallel output path along the sequence axis), not a gradient sync,
# so it does not belong behind the bucketing engine
ALLOWED_SCATTER = {
    _p("apex_tpu", "parallel", "distributed.py"),
    _p("apex_tpu", "transformer", "context_parallel.py"),
    # the jaxpr-collectives rule's own planted-violation selfcheck — a
    # deliberately-unrouted scatter inside a tiny fixture program, the
    # very thing the program-level lint exists to catch
    _p("apex_tpu", "analysis", "program.py"),
}

# modules whose psums are gradient-path reductions by construction: any
# raw lax.psum / lax.psum_scatter here must route through the
# parallel/distributed.py chokepoints (allreduce_grads / grouped_psum /
# reduce_scatter_grads) so bucketing policy cannot be bypassed
GRAD_SYNC_PREFIXES = (
    _p("apex_tpu", "training.py"),
    _p("apex_tpu", "optimizers") + os.sep,
)

_GATHER = re.compile(r"lax\.all_gather\s*\(")
_SCATTER = re.compile(r"lax\.psum_scatter\s*\(")
_PSUM = re.compile(r"lax\.psum\s*\(")


def _line_hits(pattern: re.Pattern, source: str):
    return [i + 1 for i, line in enumerate(source.splitlines())
            if pattern.search(line)]


def rule_collectives(repo: str) -> Findings:
    findings, notes = [], []
    for path in iter_py_files(os.path.join(repo, PACKAGE)):
        rel = os.path.relpath(path, repo)
        with open(path) as f:
            source = f.read()

        hits = _line_hits(_GATHER, source)
        if hits:
            if rel in ALLOWED_GATHER:
                notes.append(f"ok       {rel}: gather wrapper module "
                             f"(lines {', '.join(map(str, hits))})")
            else:
                findings.extend(Finding(
                    "ast-collectives", "RAW", f"{rel}:{ln}",
                    "lax.all_gather outside the VMA-safe wrappers — use "
                    "apex_tpu.utils.vma.varying_all_gather (or "
                    "invariant_all_gather)") for ln in hits)

        hits = _line_hits(_SCATTER, source)
        if hits:
            if rel in ALLOWED_SCATTER:
                notes.append(f"ok       {rel}: psum_scatter chokepoint/"
                             f"allowlisted "
                             f"(lines {', '.join(map(str, hits))})")
            else:
                findings.extend(Finding(
                    "ast-collectives", "RAW", f"{rel}:{ln}",
                    "lax.psum_scatter outside the grad-sync chokepoint — "
                    "use apex_tpu.parallel.distributed."
                    "reduce_scatter_grads (bucketing/telemetry ride on "
                    "it)") for ln in hits)

        if rel.startswith(GRAD_SYNC_PREFIXES):
            findings.extend(Finding(
                "ast-collectives", "RAW", f"{rel}:{ln}",
                "raw lax.psum in a grad-sync module — route through "
                "apex_tpu.parallel.distributed (allreduce_grads / "
                "grouped_psum) so bucketing policy and ddp/* telemetry "
                "cannot be bypassed") for ln in _line_hits(_PSUM, source))
    return findings, notes


# ---------------------------------------------------------------------------
# ast-metrics-doc + ast-metric-families: the metric-name contracts
# ---------------------------------------------------------------------------

DOC = os.path.join("docs", "OBSERVABILITY.md")

# metric families under the documentation contract; names outside these
# prefixes (host registry internals, ad-hoc example metrics) are exempt
# from the PER-NAME doc check — but see EXEMPT_PREFIXES: the family
# meta-lint requires every slash-prefixed name to belong somewhere.
METRIC_PREFIXES = ("health/", "tp/", "amp/", "ddp/", "pipeline/",
                   "optim/", "zero/", "mem/", "perf/", "ckpt/", "resume/",
                   "serve/", "slo/", "elastic/", "fleet/", "train/")

# slash-prefixed families that are deliberately OUTSIDE the doc-table
# contract: jax/* (the compile-storm counters install_compile_listeners
# owns) and memory/* (raw allocator passthrough from sample_memory_stats)
# are runtime internals documented in prose, not per-name table rows
EXEMPT_PREFIXES = ("jax/", "memory/")

# callees whose literal first argument is a metric name: in-graph
# ``ingraph.record(...)`` and the host-registry accessors — ``gauge``
# (the mem/* family is static per compile, so it rides gauges, not
# records) plus ``counter``/``histogram``, which the elastic runtime's
# ckpt/* and resume/* families ride
METRIC_CALLEES = ("record", "gauge", "counter", "histogram")

_PLACEHOLDER = re.compile(r"<[^<>`]*>")


def _norm(name: str) -> str:
    """Collapse every ``<...>`` placeholder spelling to ``<>`` so the
    source's ``f"health/{name}/l2"`` matches the doc's
    ``health/<tree>/l2``."""
    return _PLACEHOLDER.sub("<>", name)


def _metric_names(repo: str):
    """Yield ``(relpath, lineno, name)`` for every statically-known
    metric name at a record/gauge/counter/histogram call site."""
    for rel, tree in iter_package_trees(repo):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if callee_name(node) not in METRIC_CALLEES:
                continue
            name = literal_str(node.args[0])
            if name is not None:
                yield rel, node.lineno, name


def _documented_names(repo: str) -> set:
    """Every backticked token in the observability doc, normalized."""
    with open(os.path.join(repo, DOC)) as f:
        text = f.read()
    return {_norm(tok) for tok in re.findall(r"`([^`\n]+)`", text)}


def rule_metrics_doc(repo: str) -> Findings:
    try:
        documented = _documented_names(repo)
    except OSError:
        return [Finding("ast-metrics-doc", "MISSING", DOC,
                        "cannot read the metric table")], []
    findings, notes = [], []
    for rel, lineno, name in _metric_names(repo):
        if not _norm(name).startswith(METRIC_PREFIXES):
            continue
        if _norm(name) in documented:
            notes.append(f"ok       {name} ({rel}:{lineno})")
        else:
            findings.append(Finding(
                "ast-metrics-doc", "UNDOC", f"{rel}:{lineno}",
                f"{name} recorded but absent from {DOC} — add a table "
                f"row (placeholders like <tree> match f-string fields)"))
    return findings, notes


def rule_metric_families(repo: str) -> Findings:
    """The meta-lint: every slash-prefixed metric name must open with a
    KNOWN family — either a documented ``METRIC_PREFIXES`` family or an
    explicitly exempt runtime-internal one. The family list used to be
    maintained by hand per PR; a brand-new ``<prefix>/`` family now
    fails here with its call site instead of shipping undocumented."""
    findings, notes = [], []
    known = METRIC_PREFIXES + EXEMPT_PREFIXES
    seen_families = set()
    for rel, lineno, name in _metric_names(repo):
        norm = _norm(name)
        if "/" not in norm or norm.startswith("<"):
            continue  # unprefixed ad-hoc names are outside the contract
        family = norm.split("/", 1)[0] + "/"
        if family in known:
            seen_families.add(family)
        else:
            findings.append(Finding(
                "ast-metric-families", "ROGUE", f"{rel}:{lineno}",
                f"{name} opens a metric family {family!r} that is in "
                f"neither METRIC_PREFIXES (documented families) nor "
                f"EXEMPT_PREFIXES — register it in "
                f"apex_tpu/analysis/rules_ast.py and document it in "
                f"{DOC}"))
    notes.append("ok       families in use: "
                 + ", ".join(sorted(seen_families)))
    return findings, notes


# ---------------------------------------------------------------------------
# ast-remat-names: checkpoint-name tags come from the registry
# ---------------------------------------------------------------------------

REGISTRY_FILE = _p(PACKAGE, "remat.py")

# callee spellings that denote a checkpoint-name tag. ``_tag`` is the
# models' policy-gated bound tagger (identity under none/full); ``tag``
# the remat-module chokepoint; ``checkpoint_name`` the raw jax call.
TAG_CALLEES = ("checkpoint_name", "tag", "_tag", "_remat_tag")


def _remat_registry(repo: str):
    """``(CHECKPOINT_NAMES, SELECTIVE_SAVE)`` parsed from the registry
    module's AST — raises OSError/ValueError when the module or the
    assignments are missing (a moved registry must move this scan too)."""
    with open(os.path.join(repo, REGISTRY_FILE)) as f:
        tree = ast.parse(f.read(), filename=REGISTRY_FILE)
    names = save = None
    for node in ast.walk(tree):
        targets = ()
        if isinstance(node, ast.Assign):
            targets = node.targets
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets = (node.target,)
        for t in targets:
            if isinstance(t, ast.Name) and t.id == "CHECKPOINT_NAMES":
                names = tuple_literal(node.value)
            if isinstance(t, ast.Name) and t.id == "SELECTIVE_SAVE":
                save = tuple_literal(node.value)
    if not names:
        raise ValueError(
            f"{REGISTRY_FILE} defines no CHECKPOINT_NAMES tuple literal")
    return tuple(names), tuple(save or ())


def _tag_sites(repo: str):
    """Yield ``(relpath, lineno, name)`` for every statically-known tag
    literal in the package (registry module excluded — its docstrings and
    error messages mention names by design)."""
    for rel, tree in iter_package_trees(repo):
        if rel == REGISTRY_FILE:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if callee_name(node) not in TAG_CALLEES:
                continue
            # the name rides as the positional second argument or as
            # the name= keyword (raw checkpoint_name accepts both)
            name = node.args[1] if len(node.args) >= 2 else next(
                (kw.value for kw in node.keywords if kw.arg == "name"),
                None)
            if isinstance(name, ast.Constant) and isinstance(
                    name.value, str):
                yield rel, node.lineno, name.value


def rule_remat_names(repo: str) -> Findings:
    try:
        names, save = _remat_registry(repo)
    except (OSError, ValueError) as e:
        return [Finding("ast-remat-names", "MISSING", "registry",
                        str(e))], []
    findings, notes = [], []
    for extra in [n for n in save if n not in names]:
        findings.append(Finding(
            "ast-remat-names", "ORPHAN", "SELECTIVE_SAVE",
            f"SELECTIVE_SAVE entry {extra!r} is not in CHECKPOINT_NAMES"))
    for rel, lineno, name in _tag_sites(repo):
        if name in names:
            notes.append(f"ok       {name} ({rel}:{lineno})")
        else:
            findings.append(Finding(
                "ast-remat-names", "ORPHAN", f"{rel}:{lineno}",
                f"{name} tagged but absent from remat.CHECKPOINT_NAMES — "
                f"no policy can save it"))
    return findings, notes


# ---------------------------------------------------------------------------
# ast-elastic-exits: the process-exit discipline
# ---------------------------------------------------------------------------

ELASTIC_DIR = _p(PACKAGE, "elastic")
CHOKEPOINT_FILE = _p(PACKAGE, "utils", "autoresume.py")
CHOKEPOINT_FUNC = "request_resume"
# the supervisor CLI (elastic/launch.py) needs a SECOND blessed exit —
# it must propagate the gang's success as a process exit code — pinned,
# exactly like the runner's, to one named chokepoint function
LAUNCH_FILE = _p(PACKAGE, "elastic", "launch.py")
LAUNCH_CHOKEPOINT_FUNC = "_supervisor_exit"


def _exit_spelling(node):
    """The process-exit spelling of an AST node, or None."""
    if isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
            if (f.value.id, f.attr) in (("sys", "exit"), ("os", "_exit"),
                                        ("os", "abort")):
                return f"{f.value.id}.{f.attr}"
        if isinstance(f, ast.Name) and f.id in ("exit", "quit"):
            return f.id
    if isinstance(node, ast.Raise) and node.exc is not None:
        exc = node.exc
        name = (exc.func if isinstance(exc, ast.Call) else exc)
        if isinstance(name, ast.Name) and name.id == "SystemExit":
            return "raise SystemExit"
    return None


def _launch_chokepoint_nodes(tree) -> set:
    """ids of every AST node inside ``LAUNCH_CHOKEPOINT_FUNC`` defs."""
    inside = set()
    for func in ast.walk(tree):
        if isinstance(func, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and func.name == LAUNCH_CHOKEPOINT_FUNC:
            inside.update(id(n) for n in ast.walk(func))
    return inside


def rule_elastic_exits(repo: str) -> Findings:
    findings, notes = [], []
    pkg = os.path.join(repo, ELASTIC_DIR)
    if not os.path.isdir(pkg):
        return [Finding("ast-elastic-exits", "MISSING", ELASTIC_DIR,
                        "elastic package absent")], []
    for path in iter_py_files(pkg):
        rel = os.path.relpath(path, repo)
        tree = parse_file(path, rel)
        if tree is None:
            continue
        is_launch = rel == LAUNCH_FILE
        blessed = _launch_chokepoint_nodes(tree) if is_launch else set()
        clean = True
        blessed_exits = 0
        for node in ast.walk(tree):
            spelling = _exit_spelling(node)
            if spelling is None:
                continue
            if id(node) in blessed:
                # the supervisor CLI's one sanctioned exit; counted and
                # shape-checked below, never reported as a raw EXIT
                if spelling == "sys.exit":
                    blessed_exits += 1
                    continue
            clean = False
            findings.append(Finding(
                "ast-elastic-exits", "EXIT", f"{rel}:{node.lineno}",
                f"{spelling}: elastic code must exit only through "
                f"AutoResume.{CHOKEPOINT_FUNC}"
                + (f" or {LAUNCH_CHOKEPOINT_FUNC} (the supervisor CLI "
                   f"chokepoint)" if is_launch else "")
                + " — raise instead, so failures stay distinguishable "
                  "from clean preemptions"))
        if is_launch:
            # chokepoint-rot check, mirroring the AutoResume one: the
            # blessed function must hold EXACTLY one sys.exit
            if blessed_exits != 1:
                clean = False
                findings.append(Finding(
                    "ast-elastic-exits", "CHOKE", rel,
                    f"expected exactly one sys.exit inside "
                    f"{LAUNCH_CHOKEPOINT_FUNC}, found {blessed_exits}"))
            else:
                notes.append(f"ok       {rel}::{LAUNCH_CHOKEPOINT_FUNC} "
                             f"is the supervisor exit chokepoint")
        if clean:
            notes.append(f"ok       {rel}")

    # the chokepoint itself: exactly one sys.exit, inside request_resume
    choke = os.path.join(repo, CHOKEPOINT_FILE)
    try:
        with open(choke) as f:
            tree = ast.parse(f.read(), filename=CHOKEPOINT_FILE)
    except OSError:
        findings.append(Finding(
            "ast-elastic-exits", "MISSING", CHOKEPOINT_FILE,
            "the AutoResume chokepoint the contract is anchored on "
            "cannot be read"))
        return findings, notes
    exits = []
    for func in [n for n in ast.walk(tree)
                 if isinstance(n, ast.FunctionDef)]:
        for node in ast.walk(func):
            if _exit_spelling(node) == "sys.exit":
                exits.append(func.name)
    if exits != [CHOKEPOINT_FUNC]:
        findings.append(Finding(
            "ast-elastic-exits", "CHOKE", CHOKEPOINT_FILE,
            f"expected exactly one sys.exit inside {CHOKEPOINT_FUNC}, "
            f"found {exits or 'none'}"))
    else:
        notes.append(f"ok       {CHOKEPOINT_FILE}::{CHOKEPOINT_FUNC} is "
                     f"the sole exit chokepoint")
    return findings, notes


# ---------------------------------------------------------------------------
# ast-bench-configs: declarative bench legs name real config fields
# ---------------------------------------------------------------------------

CONFIG_CLASSES = ("TrainConfig", "ModelConfig", "ParallelConfig",
                  "BatchConfig", "OptimizerConfig")
SECTIONS = {"model": "ModelConfig", "parallel": "ParallelConfig",
            "batch": "BatchConfig", "optimizer": "OptimizerConfig"}

# the request-latency vocabulary bench.py's stated DECODE_SLO may target
# (mirrors apex_tpu.observability.slo.LATENCY_METRICS — duplicated here
# because the AST family must not import the jax-backed package; the
# mirror is pinned equal in tests/test_analysis.py)
SLO_METRICS = ("queue_wait_ms", "ttft_ms", "tpot_ms", "e2e_ms")


def _dataclass_fields(path: str, class_names) -> dict:
    """``{class_name: {field, ...}}`` from annotated class-body
    assignments (the dataclass field syntax), no import needed."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    out = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name in class_names:
            fields = set()
            for stmt in node.body:
                if isinstance(stmt, ast.AnnAssign) \
                        and isinstance(stmt.target, ast.Name):
                    fields.add(stmt.target.id)
            out[node.name] = fields
    return out


def bench_field_tables(repo: str) -> dict:
    tables = _dataclass_fields(
        os.path.join(repo, PACKAGE, "config.py"), CONFIG_CLASSES)
    tables.update(_dataclass_fields(
        os.path.join(repo, PACKAGE, "models", "gpt.py"), ("GPTConfig",)))
    missing = [c for c in (*CONFIG_CLASSES, "GPTConfig")
               if not tables.get(c)]
    if missing:
        raise ValueError(f"could not extract fields for {missing}")
    return tables


def _check_spec(spec: dict, tables: dict, where: str,
                findings: list) -> bool:
    """One TrainConfig-shaped nested dict against the field tables."""
    ok = True
    for key, value in spec.items():
        if key not in tables["TrainConfig"]:
            ok = False
            findings.append(Finding(
                "ast-bench-configs", "UNKNOWN", where,
                f"{key!r} is not a TrainConfig field"))
            continue
        section = SECTIONS.get(key)
        if section and isinstance(value, dict):
            for sub in value:
                if sub not in tables[section]:
                    ok = False
                    findings.append(Finding(
                        "ast-bench-configs", "UNKNOWN", where,
                        f"{key}.{sub!r} is not a {section} field"))
    return ok


def _literal_assign(path: str, name: str):
    """The literal value of module-level ``name = <literal>``, or None."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for target in node.targets:
                if isinstance(target, ast.Name) and target.id == name:
                    return ast.literal_eval(node.value)
    return None


def _bench_table(bench_path: str):
    """The literal ``BENCH_TRAIN_CONFIGS`` dict from bench.py, or None."""
    return _literal_assign(bench_path, "BENCH_TRAIN_CONFIGS")


def _decode_slo_table(bench_path: str):
    """The literal ``DECODE_SLO`` tuple from bench.py, or None."""
    return _literal_assign(bench_path, "DECODE_SLO")


def _class_init_params(path: str, class_name: str):
    """Parameter names of ``class_name.__init__`` (AST, no import), or
    None when the class or its ``__init__`` is absent."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == class_name:
            for stmt in node.body:
                if isinstance(stmt, ast.FunctionDef) \
                        and stmt.name == "__init__":
                    a = stmt.args
                    return {p.arg for p in (*a.posonlyargs, *a.args,
                                            *a.kwonlyargs)} - {"self"}
    return None


def _check_decode_configs(repo: str, bench_path: str, findings: list,
                          notes: list):
    """The serving legs: ``BENCH_DECODE_CONFIGS`` keys must be real
    engine-constructor parameters — bench.py builds the engine by
    ``**spec``, so an unknown key would TypeError only at bench runtime
    (and a renamed engine knob would silently strand the leg). A leg
    that states ``speculate_k`` must state it >= 1 — ``speculate_k=0``
    would silently bench the non-speculative path against itself."""
    engine_path = os.path.join(repo, PACKAGE, "serving", "engine.py")
    try:
        allowed = _class_init_params(engine_path, "ServingEngine")
        table = _literal_assign(bench_path, "BENCH_DECODE_CONFIGS")
    except (OSError, SyntaxError, ValueError) as e:
        findings.append(Finding("ast-bench-configs", "MISSING",
                                "bench.py BENCH_DECODE_CONFIGS", str(e)))
        return
    if allowed is None:
        findings.append(Finding(
            "ast-bench-configs", "MISSING", "serving/engine.py",
            "no ServingEngine.__init__ to validate "
            "BENCH_DECODE_CONFIGS against"))
        return
    if table is None:
        findings.append(Finding(
            "ast-bench-configs", "MISSING", "bench.py",
            "no literal BENCH_DECODE_CONFIGS table (the serving decode "
            "legs must state their engine config declaratively)"))
        return
    for leg, spec in table.items():
        where = f"bench.py BENCH_DECODE_CONFIGS[{leg!r}]"
        if not isinstance(spec, dict):
            findings.append(Finding(
                "ast-bench-configs", "UNKNOWN", where,
                f"expected a dict of engine kwargs, got "
                f"{type(spec).__name__}"))
            continue
        bad = [k for k in spec if k not in allowed]
        if bad:
            findings.append(Finding(
                "ast-bench-configs", "UNKNOWN", where,
                f"{bad} are not ServingEngine.__init__ parameters"))
            continue
        sk = spec.get("speculate_k")
        if sk is not None and (not isinstance(sk, int) or sk < 1):
            findings.append(Finding(
                "ast-bench-configs", "UNKNOWN", where,
                f"speculate_k={sk!r}: a speculative leg must state a "
                "static draft window >= 1 (0 benches the "
                "non-speculative path against itself)"))
            continue
        notes.append(f"ok       {where}: {len(spec)} keys")


def _check_decode_slo(bench_path: str, findings: list, notes: list):
    """The stated-SLO contract: ``DECODE_SLO`` entries are
    ``(metric, quantile, threshold_ms)`` triples over the request-record
    latency vocabulary — a typo'd metric would score ``goodput`` against
    a field ``SLOTarget`` rejects only at bench runtime."""
    try:
        table = _decode_slo_table(bench_path)
    except (OSError, SyntaxError, ValueError) as e:
        findings.append(Finding("ast-bench-configs", "MISSING",
                                "bench.py DECODE_SLO", str(e)))
        return
    if table is None:
        findings.append(Finding(
            "ast-bench-configs", "MISSING", "bench.py",
            "no literal DECODE_SLO table (the gpt_decode_goodput line "
            "must state its SLO declaratively)"))
        return
    if not isinstance(table, (list, tuple)):
        # a malformed literal must be a FINDING, not a TypeError that
        # aborts the whole analysis run
        findings.append(Finding(
            "ast-bench-configs", "UNKNOWN", "bench.py DECODE_SLO",
            f"expected a tuple of (metric, quantile, threshold_ms) "
            f"triples, got {type(table).__name__}"))
        return
    ok = True
    for entry in table:
        where = f"bench.py DECODE_SLO[{entry!r}]"
        if not (isinstance(entry, tuple) and len(entry) == 3):
            ok = False
            findings.append(Finding(
                "ast-bench-configs", "UNKNOWN", where,
                "expected a (metric, quantile, threshold_ms) triple"))
            continue
        metric, quantile, threshold = entry
        if metric not in SLO_METRICS:
            ok = False
            findings.append(Finding(
                "ast-bench-configs", "UNKNOWN", where,
                f"{metric!r} is not a request-latency metric "
                f"{SLO_METRICS}"))
        if not (isinstance(quantile, (int, float))
                and 0 < quantile < 100):
            ok = False
            findings.append(Finding(
                "ast-bench-configs", "UNKNOWN", where,
                f"quantile {quantile!r} outside (0, 100)"))
        if not (isinstance(threshold, (int, float)) and threshold > 0):
            ok = False
            findings.append(Finding(
                "ast-bench-configs", "UNKNOWN", where,
                f"threshold_ms {threshold!r} must be positive"))
    if ok:
        notes.append(f"ok       bench.py DECODE_SLO: {len(table)} "
                     f"target(s)")


def _gpt_step_calls(bench_path: str):
    """``(own_params, [(lineno, kw_names)])`` of every
    ``_gpt_train_step(...)`` call plus the def's own parameters."""
    with open(bench_path) as f:
        tree = ast.parse(f.read(), filename=bench_path)
    own_params = set()
    calls = []
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) \
                and node.name == "_gpt_train_step":
            a = node.args
            own_params = {p.arg for p in
                          (*a.posonlyargs, *a.args, *a.kwonlyargs)}
        if isinstance(node, ast.Call):
            if callee_name(node) == "_gpt_train_step":
                kws = [k.arg for k in node.keywords if k.arg is not None]
                calls.append((node.lineno, kws))
    return own_params, calls


def rule_bench_configs(repo: str) -> Findings:
    findings, notes = [], []
    try:
        tables = bench_field_tables(repo)
    except (OSError, ValueError) as e:
        return [Finding("ast-bench-configs", "MISSING",
                        "config field tables", str(e))], []

    bench_path = os.path.join(repo, "bench.py")
    try:
        table = _bench_table(bench_path)
        own_params, calls = _gpt_step_calls(bench_path)
    except (OSError, SyntaxError, ValueError) as e:
        return [Finding("ast-bench-configs", "MISSING", "bench.py",
                        str(e))], []
    if table is None:
        findings.append(Finding(
            "ast-bench-configs", "MISSING", "bench.py",
            "no literal BENCH_TRAIN_CONFIGS table"))
    else:
        for leg, spec in table.items():
            where = f"bench.py BENCH_TRAIN_CONFIGS[{leg!r}]"
            if _check_spec(spec, tables, where, findings):
                nkeys = sum(len(v) if isinstance(v, dict) else 1
                            for v in spec.values())
                notes.append(f"ok       {where}: {nkeys} keys")

    _check_decode_slo(bench_path, findings, notes)
    _check_decode_configs(repo, bench_path, findings, notes)

    allowed = own_params | tables["GPTConfig"]
    for lineno, kws in calls:
        bad = [k for k in kws if k not in allowed]
        if bad:
            findings.append(Finding(
                "ast-bench-configs", "UNKNOWN", f"bench.py:{lineno}",
                f"_gpt_train_step keyword(s) {bad} match neither its "
                f"parameters nor a GPTConfig field"))
        else:
            notes.append(f"ok       bench.py:{lineno} _gpt_train_step "
                         f"call")

    results_path = os.path.join(repo, "BENCH_CONFIGS.json")
    if os.path.exists(results_path):
        try:
            with open(results_path) as f:
                entries = json.load(f)
        except (OSError, ValueError) as e:
            findings.append(Finding(
                "ast-bench-configs", "MISSING", "BENCH_CONFIGS.json",
                str(e)))
            return findings, notes
        for entry in entries if isinstance(entries, list) else []:
            cfg = entry.get("config") if isinstance(entry, dict) else None
            if isinstance(cfg, dict):
                where = (f"BENCH_CONFIGS.json "
                         f"[{entry.get('metric', '?')}].config")
                if _check_spec(cfg, tables, where, findings):
                    notes.append(f"ok       {where}")
    return findings, notes


# ---------------------------------------------------------------------------
# ast-bench-history: the perfwatch JSONL schema stays pinned to its writer
# ---------------------------------------------------------------------------

PERFWATCH_FILE = _p(PACKAGE, "observability", "perfwatch.py")
HISTORY_TABLE = "HISTORY_FIELDS"
HISTORY_WRITER = "make_record"
HISTORY_JSONL = "BENCH_HISTORY.jsonl"


def _history_writer_keys(path: str):
    """``(base_keys, promoted_keys)`` of the history writer: the literal
    keys of ``make_record``'s record dict (the always-present set) and
    every literal ``rec["..."] = ...`` subscript it assigns (the
    conditionally-promoted set). None when the function is absent."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    for node in ast.walk(tree):
        if not (isinstance(node, ast.FunctionDef)
                and node.name == HISTORY_WRITER):
            continue
        base, promoted = [], []
        for sub in ast.walk(node):
            if isinstance(sub, ast.Dict):
                keys = [k.value for k in sub.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)]
                if "metric" in keys:
                    base = keys
            if isinstance(sub, ast.Assign):
                for t in sub.targets:
                    if isinstance(t, ast.Subscript) \
                            and isinstance(t.slice, ast.Constant) \
                            and isinstance(t.slice.value, str):
                        promoted.append(t.slice.value)
        return base, promoted
    return None


def rule_bench_history(repo: str) -> Findings:
    """The longitudinal twin of ``ast-bench-configs``: perfwatch's
    ``HISTORY_FIELDS`` literal is the one schema every
    ``BENCH_HISTORY.jsonl`` record obeys — the writer's always-present
    dict keys must equal the table's ``required`` set, its promoted
    keys must come from the table, and any on-disk history at the repo
    root must match both (a key outside the table means a reader and a
    writer already disagree)."""
    findings, notes = [], []
    path = os.path.join(repo, PERFWATCH_FILE)
    try:
        table = _literal_assign(path, HISTORY_TABLE)
        writer = _history_writer_keys(path)
    except (OSError, SyntaxError, ValueError) as e:
        return [Finding("ast-bench-history", "MISSING", PERFWATCH_FILE,
                        str(e))], []
    if table is None:
        return [Finding(
            "ast-bench-history", "MISSING", PERFWATCH_FILE,
            f"no literal {HISTORY_TABLE} table (the JSONL schema must "
            f"be stated declaratively)")], []

    fields, required, ok_shape = {}, set(), True
    for entry in table:
        if not (isinstance(entry, tuple) and len(entry) == 2
                and isinstance(entry[0], str)
                and entry[1] in ("required", "optional")):
            ok_shape = False
            findings.append(Finding(
                "ast-bench-history", "UNKNOWN",
                f"{HISTORY_TABLE}[{entry!r}]",
                "expected a (field, 'required'|'optional') pair"))
            continue
        fields[entry[0]] = entry[1]
        if entry[1] == "required":
            required.add(entry[0])
    if ok_shape:
        notes.append(f"ok       {HISTORY_TABLE}: {len(fields)} field(s), "
                     f"{len(required)} required")

    if writer is None:
        findings.append(Finding(
            "ast-bench-history", "MISSING", PERFWATCH_FILE,
            f"no {HISTORY_WRITER}() writer to validate "
            f"{HISTORY_TABLE} against"))
    else:
        base, promoted = writer
        for key in sorted(required - set(base)):
            findings.append(Finding(
                "ast-bench-history", "MISSING",
                f"{PERFWATCH_FILE}::{HISTORY_WRITER}",
                f"required field {key!r} absent from the writer's "
                f"record literal"))
        for key in sorted(set(base) - required):
            findings.append(Finding(
                "ast-bench-history", "ROGUE",
                f"{PERFWATCH_FILE}::{HISTORY_WRITER}",
                f"writer always emits {key!r}, which {HISTORY_TABLE} "
                f"does not list as required"))
        for key in sorted(set(promoted) - set(fields)):
            findings.append(Finding(
                "ast-bench-history", "ROGUE",
                f"{PERFWATCH_FILE}::{HISTORY_WRITER}",
                f"writer promotes {key!r}, which is not in "
                f"{HISTORY_TABLE} at all"))
        if set(base) == required and set(promoted) <= set(fields):
            notes.append(f"ok       {HISTORY_WRITER}: {len(base)} base + "
                         f"{len(set(promoted))} promoted key(s) match")

    jsonl = os.path.join(repo, HISTORY_JSONL)
    if os.path.exists(jsonl):
        checked = 0
        with open(jsonl) as f:
            for lineno, line in enumerate(f, 1):
                line = line.strip()
                if not line:
                    continue
                where = f"{HISTORY_JSONL}:{lineno}"
                try:
                    rec = json.loads(line)
                except ValueError as e:
                    findings.append(Finding(
                        "ast-bench-history", "UNKNOWN", where, str(e)))
                    continue
                keys = set(rec) if isinstance(rec, dict) else set()
                for key in sorted(required - keys):
                    findings.append(Finding(
                        "ast-bench-history", "MISSING", where,
                        f"record lacks required field {key!r}"))
                for key in sorted(keys - set(fields)):
                    findings.append(Finding(
                        "ast-bench-history", "UNKNOWN", where,
                        f"record key {key!r} is not in {HISTORY_TABLE}"))
                checked += 1
        notes.append(f"ok       {HISTORY_JSONL}: {checked} record(s) "
                     f"checked")
    return findings, notes


# ---------------------------------------------------------------------------
# registration
# ---------------------------------------------------------------------------

register(Rule("ast-annotations", "ast",
              "documented hot-path named_scope annotations exist in "
              "source (pyprof region vocabulary)", run=rule_annotations))
register(Rule("ast-collectives", "ast",
              "collective call sites stay behind the VMA/bucketing "
              "chokepoints (text-level; jaxpr-collectives is the "
              "program-level twin)", run=rule_collectives))
register(Rule("ast-metrics-doc", "ast",
              "every recorded metric name in a checked family has a "
              "docs/OBSERVABILITY.md row", run=rule_metrics_doc))
register(Rule("ast-metric-families", "ast",
              "every slash-prefixed metric name belongs to a registered "
              "family (meta-lint over the family list itself)",
              run=rule_metric_families))
register(Rule("ast-remat-names", "ast",
              "checkpoint-name tag literals come from "
              "remat.CHECKPOINT_NAMES; SELECTIVE_SAVE is a registry "
              "subset", run=rule_remat_names))
register(Rule("ast-elastic-exits", "ast",
              "elastic code exits only through AutoResume.request_resume "
              "or launch.py::_supervisor_exit", run=rule_elastic_exits))
register(Rule("ast-bench-configs", "ast",
              "bench-config keys name real config dataclass fields",
              run=rule_bench_configs))
register(Rule("ast-bench-history", "ast",
              "the perfwatch JSONL schema (writer keys + on-disk "
              "records) matches the literal HISTORY_FIELDS table",
              run=rule_bench_history))

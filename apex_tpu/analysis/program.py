"""Family-A rules: jaxpr/program lints for the bug classes found the
hard way.

Every rule here encodes a production bug this repo actually shipped and
caught late with a hand-written one-off check (pointers in
docs/ANALYSIS.md):

- :func:`check_donation` — PR 9's double-donated shared int8 scale
  buffer: a pytree leaf appearing twice in a donated argument hands the
  SAME buffer to XLA twice (use-after-free class), and a donated leaf
  that never shows up in ``input_output_alias`` silently wastes the
  in-place-update HBM saving the donation was for.
- :func:`check_collective_placement` — the program-level twin of
  ``scripts/check_collectives.py``: a helper that *calls* ``lax.psum``
  indirectly escapes the AST check, but its equation still lands in the
  jaxpr outside the blessed chokepoint ``named_scope``\\ s.
- :func:`check_flat_materialization` — PR 8's flat-gradient barrier: a
  1-D padded-size fp32 value anywhere in a bucketed ZeRO program is the
  full-tree ravel barrier back in disguise.
- :func:`check_shared_grad_reduction` — PR 7's silent shared-param
  cotangent drift: under ``shard_map_unchecked`` on pre-VMA jax there is
  no replication rewrite, so a replicated param's cotangent with no
  reducing collective over the mesh axis in its dependency cone is a
  per-rank partial — every rank steps with a different gradient.
- :class:`recompile_guard` — PR 1's compile-storm counters as a scoped
  assertion: the serving/elastic driver loops adopt it so a shape or
  static-arg leak that retraces the steady-state step fails loudly.

Rules return :class:`~apex_tpu.analysis.core.Finding` lists; the
``verify_*`` convenience wrappers raise :class:`AnalysisError` instead
(construction-time self-checks). Each rule registers a CLI ``selfcheck``
proving itself on a built-in clean/planted program pair.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence, Tuple

from apex_tpu.analysis.core import (AnalysisError, Finding, Rule,
                                    format_finding, register)
from apex_tpu.analysis import jaxpr as jx

__all__ = ["DEFAULT_BLESSED_SCOPES", "GRAD_SYNC_COLLECTIVES",
           "check_donation", "check_collective_placement",
           "check_flat_materialization", "check_shared_grad_reduction",
           "verify_findings", "lint_program", "recompile_guard",
           "lint_trainer_step", "lint_serving_engine"]


# ---------------------------------------------------------------------------
# jaxpr-donation
# ---------------------------------------------------------------------------

import re as _re

# HLO header: input_output_alias={ {0}: (1, {}, may-alias), ... } — the
# parenthesized first field is the parameter number
_HLO_ALIAS_KEY = "input_output_alias={"
_HLO_PARAM = _re.compile(r"\(\s*(\d+)\s*,")
# StableHLO (lowered, pre-XLA): each aliased parameter carries a
# tf.aliasing_output attr; a requested-but-unpaired donation shows up as
# jax.buffer_donor (or the parameter is dropped entirely when unused)
_SH_ALIAS = "tf.aliasing_output"
_SH_DONOR = "jax.buffer_donor"


def _alias_param_numbers(text: str) -> Optional[List[int]]:
    """Parameter numbers aliased in an HLO module header, or None when
    the text is not HLO (StableHLO lowered text has no header map). The
    map nests braces (output/param tuple indices), so the span is found
    by balance, not regex."""
    start = text.find(_HLO_ALIAS_KEY)
    if start < 0:
        return None
    i = start + len(_HLO_ALIAS_KEY)
    depth = 1
    j = i
    while j < len(text) and depth:
        depth += {"{": 1, "}": -1}.get(text[j], 0)
        j += 1
    return [int(p) for p in _HLO_PARAM.findall(text[i:j])]


def _buffer_key(leaf) -> tuple:
    """An identity key for a device buffer: the array object itself,
    plus the raw buffer pointer when the backend exposes one (two
    distinct jax.Array wrappers can share a buffer)."""
    try:
        return ("ptr", leaf.unsafe_buffer_pointer())
    except Exception:
        return ("id", id(leaf))


def check_donation(program: Any = None, *,
                   donated_args: Any = None,
                   expected_donated: Optional[int] = None,
                   min_alias_bytes: Optional[int] = None,
                   label: str = "program") -> List[Finding]:
    """Donation-safety lint.

    ``program``: a lowered or compiled stage (anything with
    ``.as_text()``); HLO headers are parsed for ``input_output_alias``
    entries, StableHLO for ``tf.aliasing_output`` parameter attributes.
    ``expected_donated``: the number of donated *leaves* the caller
    annotated (e.g. ``len(tree_leaves(cache))``) — fewer aliased
    parameters than that means a donated buffer is NOT updated in place.
    ``donated_args``: the actual argument pytree(s) that will be donated
    — flagged when two leaves are the same underlying buffer (the PR 9
    double-donation class; XLA cannot see this statically).
    ``min_alias_bytes``: floor on ``memory_analysis().alias_size_in_bytes``
    for compiled programs (skipped where the backend reports none).
    """
    findings: List[Finding] = []

    if donated_args is not None:
        import jax
        seen = {}
        for args in (donated_args if isinstance(donated_args, tuple)
                     else (donated_args,)):
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                    args)[0]:
                if not hasattr(leaf, "dtype"):
                    continue
                key = _buffer_key(leaf)
                pretty = jax.tree_util.keystr(path)
                if key in seen:
                    findings.append(Finding(
                        "jaxpr-donation", "DOUBLE", label,
                        f"leaves {seen[key]} and {pretty} are the SAME "
                        f"buffer donated twice — XLA will alias one "
                        f"buffer to two outputs (the PR 9 shared-scale "
                        f"class); allocate distinct buffers"))
                else:
                    seen[key] = pretty

    if program is not None:
        text = program.as_text() if hasattr(program, "as_text") else \
            str(program)
        params = _alias_param_numbers(text)
        if params is None:
            n_aliased = text.count(_SH_ALIAS)
            n_unpaired = text.count(_SH_DONOR)
            if n_unpaired:
                findings.append(Finding(
                    "jaxpr-donation", "UNALIASED", label,
                    f"{n_unpaired} donated parameter(s) carry "
                    f"{_SH_DONOR} but no {_SH_ALIAS} — the donation "
                    f"could not be paired with an output and buys "
                    f"nothing"))
        else:
            n_aliased = len(params)
            dup = sorted({p for p in params if params.count(p) > 1})
            if dup:
                findings.append(Finding(
                    "jaxpr-donation", "DOUBLE", label,
                    f"parameter(s) {dup} appear in more than one "
                    f"input_output_alias entry — one donated buffer "
                    f"feeds two outputs"))
        if expected_donated is not None and n_aliased < expected_donated:
            findings.append(Finding(
                "jaxpr-donation", "UNALIASED", label,
                f"only {n_aliased} of {expected_donated} donated leaves "
                f"appear in the program's input/output aliasing — the "
                f"rest are copied, not updated in place (an unused "
                f"donated arg is dropped from the program entirely)"))
        ma = getattr(program, "memory_analysis", None)
        if min_alias_bytes is not None and callable(ma):
            try:
                analysis = ma()
            except Exception:
                analysis = None
            if analysis is not None:
                got = int(getattr(analysis, "alias_size_in_bytes", 0))
                # an executable deserialized from the PERSISTENT
                # compilation cache carries NO memory_analysis —
                # alias_size reads 0 while the HLO header's
                # input_output_alias map (parsed above) is intact and
                # complete. The map is the authority there; a 0 next to
                # a complete map is missing metadata, not a missing
                # alias (reproduced: fresh compile 4096 bytes, cache
                # hit 0 bytes, identical alias map — this hard-failed
                # the dryrun serving leg on every warm-cache retry).
                # A genuinely partial alias (0 < got < floor) still
                # fires.
                map_complete = n_aliased > 0 and (
                    expected_donated is None
                    or n_aliased >= expected_donated)
                if got < min_alias_bytes and not (
                        got == 0 and map_complete):
                    findings.append(Finding(
                        "jaxpr-donation", "UNALIASED", label,
                        f"alias_size_in_bytes {got} < expected "
                        f"{min_alias_bytes} — part of the donated state "
                        f"is still copied each step"))
    return findings


# ---------------------------------------------------------------------------
# jaxpr-collectives
# ---------------------------------------------------------------------------

# chokepoint named_scopes a grad-sync collective may live under: the DDP
# engine's own scopes plus optimizer_step (ZeRO's per-bucket RS/AG issue
# from inside the optimizer; scripts/check_annotations.py pins all three
# scopes to their owning modules)
DEFAULT_BLESSED_SCOPES = ("apex_ddp_allreduce",
                          "apex_ddp_bucketed_allreduce", "optimizer_step")

# the grad-sync collective class the placement lint polices by default;
# bare psum is NOT here (loss means / metrics / health psums are
# legitimate everywhere) — pass collectives=(..., "psum") to tighten a
# specific program
GRAD_SYNC_COLLECTIVES = ("psum_scatter", "reduce_scatter", "all_gather",
                         "all_gather_invariant")


def check_collective_placement(
        program: Any, *, blessed: Sequence[str] = DEFAULT_BLESSED_SCOPES,
        collectives: Sequence[str] = GRAD_SYNC_COLLECTIVES,
        axes: Optional[Sequence[str]] = None,
        label: str = "program") -> List[Finding]:
    """Flag ``collectives``-class equations (optionally restricted to
    mesh ``axes``) whose accumulated ``named_scope`` stack contains none
    of the ``blessed`` chokepoint scopes. Catches what the AST check
    cannot: a helper that reaches ``lax.psum_scatter`` through any number
    of indirections still traces to an equation outside the scope."""
    findings = []
    jaxpr = jx.jaxpr_of(program)
    for eqn, stack in jx.iter_eqns_scoped(jaxpr):
        name = eqn.primitive.name
        if name not in collectives:
            continue
        eq_axes = jx.eqn_axes(eqn)
        if axes is not None and not set(eq_axes) & set(axes):
            continue
        if not jx.scope_matches(stack, blessed):
            findings.append(Finding(
                "jaxpr-collectives", "RAW", label,
                f"{name} over axes {tuple(eq_axes)} outside the blessed "
                f"chokepoint scopes {tuple(blessed)} (scope stack: "
                f"{stack or '<none>'}) — route it through the "
                f"parallel/distributed chokepoints or extend the "
                f"blessed list with justification"))
    return findings


# ---------------------------------------------------------------------------
# jaxpr-flat-grad
# ---------------------------------------------------------------------------

def check_flat_materialization(program: Any, sizes, *,
                               dtype: str = "float32",
                               label: str = "program") -> List[Finding]:
    """No equation in a bucketed/ZeRO program may output a 1-D ``dtype``
    array of a padded flat-gradient ``size`` — that value IS the
    full-tree ravel barrier the backward-interleaved apply removed
    (PR 8); its presence serializes every bucket behind the slowest."""
    jaxpr = jx.jaxpr_of(program)
    if isinstance(sizes, int):
        sizes = (sizes,)
    findings = []
    for size in sizes:
        prims = jx.flat_materializations(jaxpr, size, dtype)
        if prims:
            findings.append(Finding(
                "jaxpr-flat-grad", "BARRIER", label,
                f"padded-size ({size},) {dtype} value(s) materialize "
                f"via {sorted(set(prims))} — the full flat gradient "
                f"barrier is back; ravel span-locally per bucket"))
    return findings


# ---------------------------------------------------------------------------
# jaxpr-shared-grad
# ---------------------------------------------------------------------------

def check_shared_grad_reduction(
        program: Any, outputs: Sequence[Tuple[int, str]], axis: str, *,
        label: str = "program") -> List[Finding]:
    """Each listed output (``(flat_output_index, human_name)``) must have
    a reducing collective over mesh ``axis`` in its dependency cone.

    This is PR 7's drift bug as a lint: under ``shard_map_unchecked`` on
    pre-VMA jax nothing reconciles a replicated param's cotangent, so a
    shared-grad (or updated-shared-param) output whose cone contains no
    ``psum``-class reduction over the axis is a per-rank partial — the
    nominally replicated leaves drift apart silently (~2·lr/step for
    tied embeddings)."""
    jaxpr = jx.jaxpr_of(program)
    findings = []
    for idx, name in outputs:
        if not jx.cone_has_reduction(jaxpr, idx, axis):
            findings.append(Finding(
                "jaxpr-shared-grad", "PARTIAL", label,
                f"output [{idx}] ({name}) has no reducing collective "
                f"over mesh axis {axis!r} in its dependency cone — under "
                f"shard_map_unchecked its value is a per-rank partial "
                f"cotangent and replicas will drift (psum the shared "
                f"grads over {axis!r}, see schedules._finalize_shared)"))
    return findings


# ---------------------------------------------------------------------------
# composition + verification helpers
# ---------------------------------------------------------------------------

def verify_findings(findings: List[Finding], context: str) -> None:
    """Raise :class:`AnalysisError` when any finding fired — the
    construction-time self-check spelling of the rules."""
    if findings:
        detail = "\n".join(format_finding(f) for f in findings)
        raise AnalysisError(
            f"static-analysis self-check failed for {context}:\n{detail}",
            findings)


def lint_program(program: Any, *,
                 blessed: Sequence[str] = DEFAULT_BLESSED_SCOPES,
                 collectives: Sequence[str] = GRAD_SYNC_COLLECTIVES,
                 collective_axes: Optional[Sequence[str]] = None,
                 flat_sizes: Sequence[int] = (),
                 flat_dtype: str = "float32",
                 shared_outputs: Sequence[Tuple[int, str]] = (),
                 shared_axis: Optional[str] = None,
                 label: str = "program") -> List[Finding]:
    """Run every applicable structural rule over one traced program and
    return the combined findings (the cross-talk surface the planted
    fixtures assert on: exactly one rule fires per planted bug)."""
    jaxpr = jx.jaxpr_of(program)
    findings = check_collective_placement(
        jaxpr, blessed=blessed, collectives=collectives,
        axes=collective_axes, label=label)
    if flat_sizes:
        findings += check_flat_materialization(
            jaxpr, flat_sizes, dtype=flat_dtype, label=label)
    if shared_outputs and shared_axis is not None:
        findings += check_shared_grad_reduction(
            jaxpr, shared_outputs, shared_axis, label=label)
    return findings


# ---------------------------------------------------------------------------
# jaxpr-recompile: the zero-recompile budget
# ---------------------------------------------------------------------------

class recompile_guard:
    """Context manager asserting the compile-storm counters (PR 1) stay
    FLAT across a driver loop.

    Installs listeners on a private registry, snapshots ``jax/compiles``
    and ``jax/traces`` at entry, and on exit emits a finding (and raises
    :class:`AnalysisError` unless ``raise_on_violation=False``) when
    either moved. Call :meth:`rebase` after the loop's warmup iteration
    — first dispatch legitimately compiles; the steady state must not.
    The serving scheduler (``SlotScheduler.run(no_recompile=True)``) and
    the elastic runner (``ElasticRunner.fit(no_recompile=True)``) wrap
    their loops in exactly this guard.
    """

    COUNTERS = ("jax/compiles", "jax/traces")

    def __init__(self, label: str = "loop",
                 raise_on_violation: bool = True):
        self.label = label
        self.raise_on_violation = raise_on_violation
        self.findings: List[Finding] = []
        self._reg = None
        self._base = {}

    def _snap(self) -> dict:
        snap = self._reg.snapshot()
        return {k: float(snap.get(k, 0.0)) for k in self.COUNTERS}

    def __enter__(self) -> "recompile_guard":
        from apex_tpu.observability.registry import MetricsRegistry
        from apex_tpu.observability.runtime import \
            install_compile_listeners
        self._reg = MetricsRegistry()
        install_compile_listeners(self._reg)
        self._base = self._snap()
        return self

    def rebase(self) -> None:
        """Re-baseline after warmup: compiles before this call are the
        expected first-dispatch cost, compiles after it are the storm."""
        self._base = self._snap()

    def __exit__(self, exc_type, exc, tb) -> bool:
        from apex_tpu.observability.runtime import \
            uninstall_compile_listeners
        now = self._snap()
        uninstall_compile_listeners(self._reg)
        if exc_type is not None:
            return False  # never mask the loop's own failure
        delta = {k: now[k] - self._base[k] for k in self.COUNTERS
                 if now[k] > self._base[k]}
        if delta:
            self.findings.append(Finding(
                "jaxpr-recompile", "STORM", self.label,
                f"compile-storm counters moved inside a zero-recompile "
                f"region: {delta} — a shape or static-arg leak is "
                f"retracing the steady-state step"))
        if self.findings and self.raise_on_violation:
            verify_findings(self.findings, f"recompile_guard "
                            f"({self.label})")
        return False


# ---------------------------------------------------------------------------
# real-program wiring: the trainer step and the serving engine
# ---------------------------------------------------------------------------

def _subtree_output_span(out_tree, index: int) -> Tuple[int, int]:
    """``(offset, count)`` of flat output leaves for element ``index`` of
    a tuple-structured output."""
    import jax
    leaves = [len(jax.tree_util.tree_leaves(t)) for t in out_tree]
    return sum(leaves[:index]), leaves[index]


def lint_trainer_step(trainer, state, tokens, targets, *,
                      donation: bool = True) -> List[Finding]:
    """Run the Family-A rules over a ``GPTHybridTrainer`` step on real
    arguments: flat-gradient barrier (ZeRO bucket layout's padded size),
    grad-sync collective placement on the data axis, shared-grad
    reduction over ``pipe``/``data`` for the updated shared params, and
    (``donation=True``) the donated-entry-point self-check on the
    COMPILED step — sharded programs pair donations with outputs at XLA
    compile time, so this half costs a backend compile; pass
    ``donation=False`` when the caller already verifies via
    ``trainer.jit_train_step(verify_donation=True)``."""
    import jax

    args = (*state, tokens, targets)
    jaxpr = jax.make_jaxpr(trainer.train_step)(*args).jaxpr
    findings = check_collective_placement(
        jaxpr, axes=("data",), label="trainer.train_step")

    layout = getattr(getattr(trainer, "opt", None), "_layout", None)
    if layout is not None:
        findings += check_flat_materialization(
            jaxpr, (layout.padded,), label="trainer.train_step")

    # updated shared params are output element 2 of
    # (loss, stage_stack, shared, opt_state, ls)
    out_shapes = jax.eval_shape(trainer.train_step, *args)
    offset, count = _subtree_output_span(out_shapes, 2)
    shared_paths = [
        jax.tree_util.keystr(p) for p, _ in
        jax.tree_util.tree_flatten_with_path(out_shapes[2])[0]]
    outputs = [(offset + i, f"new shared{shared_paths[i]}")
               for i in range(count)]
    mesh_axes = dict(zip(trainer.mesh.axis_names, trainer.mesh.devices.shape)) \
        if hasattr(trainer, "mesh") else {}
    for axis in ("pipe", "data"):
        if mesh_axes.get(axis, 1) > 1:
            findings += check_shared_grad_reduction(
                jaxpr, outputs, axis, label="trainer.train_step")

    if donation:
        compiled = jax.jit(trainer.train_step, donate_argnums=(0, 1, 2)
                           ).trace(*args).lower().compile()
        expected = sum(len(jax.tree_util.tree_leaves(s))
                       for s in state[:3])
        findings += check_donation(
            compiled, donated_args=tuple(state[:3]),
            expected_donated=expected, label="trainer.jit_train_step")
    else:
        findings += check_donation(donated_args=tuple(state[:3]),
                                   label="trainer.jit_train_step args")
    return findings


def lint_serving_engine(engine) -> List[Finding]:
    """Donation safety over the AOT serving programs (prefill / decode /
    release — plus ``verify`` on a speculative engine — all with the
    donated pool) plus grad-sync collective placement on the decode
    program (a serving step has no business reducing gradients at
    all)."""
    import jax
    cache = engine.cache
    n = len(jax.tree_util.tree_leaves(cache))
    nbytes = cache.nbytes()
    findings = check_donation(donated_args=cache,
                              label="ServingEngine.cache")
    programs = [("prefill", engine.prefill_compiled),
                ("decode", engine.decode_compiled),
                ("release", engine.release_compiled)]
    if engine.verify_compiled is not None:
        programs.append(("verify", engine.verify_compiled))
    for name, compiled in programs:
        findings += check_donation(
            compiled, expected_donated=n, min_alias_bytes=nbytes,
            label=f"ServingEngine.{name}")
    findings += check_collective_placement(
        engine.decode_traced, axes=None, label="ServingEngine.decode")
    if engine.verify_traced is not None:
        findings += check_collective_placement(
            engine.verify_traced, axes=None, label="ServingEngine.verify")
    return findings


# ---------------------------------------------------------------------------
# CLI selfchecks: tiny clean/planted program pairs per rule
# ---------------------------------------------------------------------------

def _one_axis_mesh(*names):
    import jax
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]).reshape(
        (1,) * len(names)), names)


def _selfcheck_donation():
    import jax
    import jax.numpy as jnp

    def clean_fn(a, b):
        return a + 1.0, b * 2.0

    def leaky_fn(a, b):
        return a + 1.0, jnp.zeros_like(b)  # b consumed, never aliased?

    a, b = jnp.arange(4.0), jnp.arange(8.0)
    clean = check_donation(
        jax.jit(clean_fn, donate_argnums=(0, 1)).trace(a, b).lower(),
        donated_args=(a, b), expected_donated=2)
    # planted: the same buffer donated twice (the PR 9 scale-plane bug)
    shared = jnp.arange(8.0)
    planted = check_donation(donated_args={"k_scale": shared,
                                           "v_scale": shared})
    # planted #2: a donated arg the program never uses -> dropped, never
    # aliased
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        lowered = jax.jit(lambda x, dead: x + 1.0,
                          donate_argnums=(0, 1)).trace(a, b).lower()
    planted += check_donation(lowered, expected_donated=2)
    return clean, planted


def _selfcheck_collectives():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = _one_axis_mesh("data")

    def body(blessed):
        def f(g):
            def sync(g):  # the indirection the AST check cannot see
                return jax.lax.psum_scatter(g, "data", tiled=True)
            if blessed:
                with jax.named_scope("optimizer_step"):
                    return sync(g)
            return sync(g)
        return shard_map(f, mesh=mesh, in_specs=P(),
                                   out_specs=P("data"))

    g = jnp.arange(8.0)
    clean = check_collective_placement(
        jax.make_jaxpr(body(True))(g).jaxpr, axes=("data",))
    planted = check_collective_placement(
        jax.make_jaxpr(body(False))(g).jaxpr, axes=("data",))
    return clean, planted


def _selfcheck_flat():
    import jax
    import jax.numpy as jnp

    g1, g2 = jnp.arange(24.0), jnp.arange(40.0)
    padded = g1.size + g2.size

    def bucketed(g1, g2):
        return jnp.sum(g1 * g1) + jnp.sum(g2 * g2)

    def barrier(g1, g2):
        flat = jnp.concatenate([g1, g2])  # the full-gradient barrier
        return jnp.sum(flat * flat)

    clean = check_flat_materialization(
        jax.make_jaxpr(bucketed)(g1, g2).jaxpr, (padded,))
    planted = check_flat_materialization(
        jax.make_jaxpr(barrier)(g1, g2).jaxpr, (padded,))
    return clean, planted


def _selfcheck_shared_grad():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    mesh = _one_axis_mesh("pipe")

    def body(reduced):
        def f(shared, x):
            def loss(s):
                return jnp.sum(jnp.tanh(x * s))
            g = jax.grad(loss)(shared)
            if reduced:
                g = jax.lax.psum(g, "pipe")
            return g
        return shard_map(f, mesh=mesh, in_specs=(P(), P()),
                                   out_specs=P())

    s, x = jnp.arange(4.0), jnp.ones(4)
    clean = check_shared_grad_reduction(
        jax.make_jaxpr(body(True))(s, x).jaxpr, [(0, "shared grad")],
        "pipe")
    planted = check_shared_grad_reduction(
        jax.make_jaxpr(body(False))(s, x).jaxpr, [(0, "shared grad")],
        "pipe")
    return clean, planted


def _selfcheck_recompile():
    import jax
    import jax.numpy as jnp

    step = jax.jit(lambda x: x * 2.0)
    step(jnp.ones(4))  # warm
    with recompile_guard("selfcheck", raise_on_violation=False) as g:
        for _ in range(3):
            step(jnp.ones(4))  # steady shape: no retrace
    clean = list(g.findings)
    with recompile_guard("selfcheck", raise_on_violation=False) as g:
        for n in (5, 6, 7):
            step(jnp.ones(n))  # shape leak: retraces every iteration
    return clean, list(g.findings)


register(Rule("jaxpr-donation", "jaxpr",
              "donated leaves are aliased in-place and no buffer is "
              "donated twice (PR 9 shared int8 scale class)",
              selfcheck=_selfcheck_donation))
register(Rule("jaxpr-collectives", "jaxpr",
              "grad-sync collectives trace inside blessed chokepoint "
              "scopes even when reached through helpers the AST check "
              "cannot see", selfcheck=_selfcheck_collectives))
register(Rule("jaxpr-flat-grad", "jaxpr",
              "no padded full-gradient vector materializes in a "
              "bucketed ZeRO program (PR 8 flat barrier class)",
              selfcheck=_selfcheck_flat))
register(Rule("jaxpr-shared-grad", "jaxpr",
              "replicated-param cotangents carry a reducing collective "
              "over the mesh axis (PR 7 shared-param drift class)",
              selfcheck=_selfcheck_shared_grad))
register(Rule("jaxpr-recompile", "jaxpr",
              "compile-storm counters stay flat across a zero-recompile "
              "driver loop (PR 1 counters as a scoped assertion)",
              selfcheck=_selfcheck_recompile))

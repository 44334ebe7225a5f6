"""Shared jaxpr-inspection helpers (promoted from ``tests/_jaxpr_utils.py``).

Three suites (parallel/DDP, collective matmul, health) pin *program shape*
— collective counts, zero-cost-off identity — on the traced jaxpr, and the
Family-A program lints in :mod:`apex_tpu.analysis.program` are built on
the same walks. The helpers live here once; ``tests/_jaxpr_utils.py`` is a
re-import shim so older test imports keep resolving:

- :func:`jaxpr_str` — trace + normalize embedded object addresses, so two
  closures tracing identical programs compare equal;
- :func:`count_primitives` — substring census over the jaxpr text (the
  cheap check: primitive names like ``psum`` / ``ppermute`` appear only as
  equation heads in jaxpr pretty-printing);
- :func:`collective_census` — the ring-decomposition census
  (ppermute / all_gather / reduce_scatter) used by the collective-matmul
  and ZeRO bucketing assertions;
- :func:`iter_eqns` / :func:`count_eqns` — structural walk over the jaxpr
  (recursing into sub-jaxprs) for assertions that need equation *params*
  (axis names, operand sizes), where text matching would be ambiguous;
- :func:`eqn_scopes` / :func:`iter_eqns_scoped` — ``named_scope``
  provenance per equation (ancestor wrapper scopes threaded into
  sub-jaxprs), the blessed-chokepoint vocabulary of the collective
  placement lint;
- :func:`cone_has_reduction` — "is there a ``psum``-class reduction over
  axis X anywhere in this output's dependency cone" — the shared-gradient
  replication-soundness walk.
"""

from __future__ import annotations

import re

import jax

__all__ = ["jaxpr_str", "count_primitives", "collective_census",
           "iter_eqns", "count_eqns", "eqn_axes", "flat_materializations",
           "sub_jaxprs", "jaxpr_of", "eqn_scopes", "iter_eqns_scoped",
           "cone_has_reduction", "REDUCING_PRIMITIVES"]


def eqn_axes(eqn) -> tuple:
    """The mesh axes a collective equation reduces over, normalized to a
    tuple of names. reduce_scatter/all_gather carry ``axis_name``; psum
    (and 0.4.x check_rep's ``psum2`` spelling) carries ``axes``."""
    ax = eqn.params.get("axis_name") or eqn.params.get("axes")
    return (ax,) if isinstance(ax, str) else tuple(ax or ())


def jaxpr_str(fn, *args) -> str:
    """Jaxpr text with embedded object addresses normalized: two trainers
    build distinct model closures, and their reprs (``<function ... at
    0x...>``) would differ even when the traced programs are identical."""
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))


def count_primitives(text: str, *names: str) -> dict:
    """``{name: substring count}`` over jaxpr text. Order names from most
    to least specific when one is a prefix of another and subtract at the
    call site (e.g. ``psum`` also matches ``psum2``-style variants)."""
    return {name: text.count(name) for name in names}


def collective_census(program) -> dict:
    """The collective census shared by the ring-decomposition and
    DP-bucketing structural tests. A (closed) jaxpr is counted by its
    equations' primitives; jaxpr TEXT by substring, which also counts
    whatever is merely NAMED for a collective (a ``custom_vjp_call`` of
    ``all_gather_matmul`` prints its function's name)."""
    names = ("ppermute", "all_gather", "reduce_scatter")
    if isinstance(program, str):
        return {name: program.count(name) for name in names}
    found = [e.primitive.name for e in iter_eqns(jaxpr_of(program))]
    return {name: found.count(name) for name in names}


def iter_eqns(jaxpr):
    """Depth-first over every equation, recursing into sub-jaxprs
    (closed call/scan/shard_map bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in sub_jaxprs(v):
                yield from iter_eqns(sub)


def sub_jaxprs(value):
    """Yield every (open) jaxpr reachable from one eqn param value."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(value, ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, Jaxpr):
        yield value
    elif isinstance(value, (list, tuple)):
        for item in value:
            yield from sub_jaxprs(item)


# kept for the legacy underscore spelling some suites imported
_sub_jaxprs = sub_jaxprs


def jaxpr_of(program, args=None):
    """The open jaxpr behind ``program``: an open ``Jaxpr`` passes
    through, a ``ClosedJaxpr`` or anything with a ``.jaxpr``
    (``jax.jit(f).trace(*args)``) unwraps, and a callable traces via
    ``jax.make_jaxpr`` when ``args`` are supplied. A bare
    ``Compiled``/``Lowered`` has already erased its jaxpr — hold the
    ``Traced`` stage instead."""
    inner = getattr(program, "jaxpr", None)
    if inner is not None and inner is not program:
        return jaxpr_of(inner)  # ClosedJaxpr / Traced -> the open jaxpr
    if hasattr(program, "eqns"):
        return program
    if callable(program) and args is not None:
        return jax.make_jaxpr(program)(*args).jaxpr
    raise TypeError(
        f"cannot recover a jaxpr from {type(program).__name__}: pass a "
        "(Closed)Jaxpr, a traced stage (jax.jit(f).trace(*args)), or a "
        "callable plus example args")


def flat_materializations(jaxpr, size, dtype="float32") -> list:
    """Primitive names of equations that OUTPUT a 1-D ``dtype`` array of
    exactly ``size`` elements — the structural detector for "the full
    padded flat gradient materialized" (the barrier the span-local
    bucketed ravel/unravel removes). Wrapper equations carrying
    sub-jaxprs (shard_map/pjit/scan/...) are excluded: their outvars are
    aggregate *views* (e.g. the global aval of a sharded ZeRO master),
    not buffers the per-device program builds — any real materialization
    inside them is a leaf equation this walk still visits."""
    out = []
    for eqn in iter_eqns(jaxpr):
        if any(True for v in eqn.params.values() for _ in sub_jaxprs(v)):
            continue
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if getattr(aval, "ndim", None) == 1 and aval.size == size \
                    and str(getattr(aval, "dtype", "")) == dtype:
                out.append(eqn.primitive.name)
    return out


def count_eqns(fn_or_jaxpr, name, *args, where=None) -> int:
    """Number of equations whose primitive is ``name``; ``where(eqn)``
    filters (e.g. on ``eqn.params['axis_name']`` or operand aval sizes).
    Pass a traceable callable plus its args, or an already-made
    (Closed)Jaxpr."""
    if callable(fn_or_jaxpr) and not hasattr(fn_or_jaxpr, "eqns"):
        jaxpr = jax.make_jaxpr(fn_or_jaxpr)(*args).jaxpr
    else:
        jaxpr = getattr(fn_or_jaxpr, "jaxpr", fn_or_jaxpr)
    return sum(1 for eqn in iter_eqns(jaxpr)
               if eqn.primitive.name == name
               and (where is None or where(eqn)))


# ---------------------------------------------------------------------------
# named_scope provenance
# ---------------------------------------------------------------------------

def eqn_scopes(eqn) -> str:
    """The ``named_scope`` stack string of one equation (empty when the
    equation was traced outside any scope). Transform wrappers may
    decorate names (``jvp(flash_attention)``); match scope names with a
    word-boundary search, not equality."""
    si = getattr(eqn, "source_info", None)
    ns = getattr(si, "name_stack", None)
    return "" if ns is None else str(ns)


def iter_eqns_scoped(jaxpr, _prefix: str = ""):
    """Depth-first ``(eqn, scope_stack_str)`` over every equation. The
    scope string accumulates ancestor wrapper equations' stacks, so an
    equation inside a scan whose *call site* sat under a scope still
    reports that scope."""
    for eqn in jaxpr.eqns:
        own = eqn_scopes(eqn)
        stack = "/".join(s for s in (_prefix, own) if s)
        yield eqn, stack
        for v in eqn.params.values():
            for sub in sub_jaxprs(v):
                yield from iter_eqns_scoped(sub, stack)


def scope_matches(stack: str, names) -> bool:
    """True when any of ``names`` appears as a whole scope word in the
    accumulated stack string."""
    return any(re.search(rf"\b{re.escape(n)}\b", stack) for n in names)


# ---------------------------------------------------------------------------
# dependency-cone reduction search (shared-gradient soundness)
# ---------------------------------------------------------------------------

# primitives that REDUCE over a mesh axis (0.4.x check_rep prints psum as
# psum2); all_gather is a broadcasting collective, not a reduction
REDUCING_PRIMITIVES = ("psum", "psum2", "psum_invariant", "psum_scatter",
                       "reduce_scatter", "all_reduce")


def _is_reduction(eqn, axis: str) -> bool:
    return (eqn.primitive.name in REDUCING_PRIMITIVES
            and axis in eqn_axes(eqn))


def _producer_map(jaxpr) -> dict:
    out = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out[v] = eqn
    return out


def cone_has_reduction(jaxpr, out_index: int, axis: str) -> bool:
    """True when a reducing collective over ``axis`` appears in the
    dependency cone of output ``out_index``.

    The walk is conservative toward *silence* (it over-approximates the
    cone rather than under-finding reductions): wrapper equations whose
    sub-jaxpr outputs align 1:1 with the equation outputs
    (pjit/shard_map/scan/closed call) are descended precisely at the
    matching output index; wrappers with no such alignment count as
    reduced if a reduction over ``axis`` appears ANYWHERE inside them;
    and the walk always continues upstream through every wrapper input.
    """
    target = jaxpr.outvars[out_index]
    return _cone_walk(jaxpr, [target], axis, set())


def _cone_walk(jaxpr, roots, axis: str, seen: set) -> bool:
    producers = _producer_map(jaxpr)
    # Literals ride in var positions and are unhashable — never producers
    stack = [v for v in roots if not hasattr(v, "val")]
    while stack:
        var = stack.pop()
        eqn = producers.get(var)
        if eqn is None:
            continue  # an input or constant of this jaxpr
        key = (id(jaxpr), id(eqn))
        if key in seen:
            continue
        seen.add(key)
        if _is_reduction(eqn, axis):
            return True
        subs = [s for v in eqn.params.values() for s in sub_jaxprs(v)]
        if subs:
            aligned = [s for s in subs
                       if len(s.outvars) == len(eqn.outvars)]
            if aligned:
                idx = list(eqn.outvars).index(var)
                for sub in aligned:
                    if _cone_walk(sub, [sub.outvars[idx]], axis, seen):
                        return True
            else:
                for sub in subs:
                    if any(_is_reduction(e, axis)
                           for e in iter_eqns(sub)):
                        return True
        stack.extend(v for v in eqn.invars if not hasattr(v, "val"))
    return False

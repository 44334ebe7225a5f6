"""Varying-manual-axes (VMA) helpers for scans inside ``shard_map``.

Under JAX's VMA type system a ``lax.scan`` carry must keep the same
varying-axes set every iteration, but a body that uses sharded params (e.g. a
TP bias add) *adds* axes to its output's set. Over-varying the carry up front
would be safe for values but makes AD insert spurious cross-replica psums
(each replica's identical loss counted once per replica), so the right fix is
the *minimal* fixed point, found by abstract evaluation.
"""

from __future__ import annotations

from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
from jax._src.lax.parallel import all_gather_invariant

__all__ = ["cast_to_vma", "scan_stable_vma", "invariant_all_gather",
           "varying_all_gather",
           "reconcile_cotangent", "restore_invariant", "leaf_vma",
           "fixed_point_vma"]


def leaf_vma(x) -> frozenset:
    """The varying-manual-axes set of a value (empty outside
    shard_map)."""
    return getattr(jax.typeof(x), "vma", None) or frozenset()


def restore_invariant(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Restore the device-INVARIANT type of a value that is replicated by
    construction but typed varying over ``axis_name``.

    The canonical case is a degenerate sharded axis: a param with in_spec
    ``P('tensor')`` is typed tensor-varying even when the axis has size 1,
    and a ``world_size == 1`` fast path that skips its closing collective
    (e.g. :class:`VocabParallelEmbedding`'s lookup) leaks that type into
    everything downstream, breaking replicated out_specs. The psum over the
    size-1 axis is a value identity that fixes the type; outside
    ``shard_map`` (empty vma) this is a no-op.
    """
    if axis_name in leaf_vma(x):
        return jax.lax.psum(x, axis_name)
    return x


def reconcile_cotangent(ct: jnp.ndarray, primal: jnp.ndarray) -> jnp.ndarray:
    """Match a ``custom_vjp`` bwd output's varying-axes type to its primal's.

    Plain-op AD under ``shard_map`` auto-pvaries a replicated operand that
    meets device-varying data, so the pvary transpose psums the cotangent
    back to the replicated total. A ``custom_vjp`` bwd rule sidesteps that
    machinery and must reconcile by hand — newer jax raises when the bwd
    output's varying axes differ from the primal's. Axes the cotangent has
    but the primal lacks are psummed (the chain-rule total for a replicated
    primal — identical to what plain AD produces); axes the primal has but
    the cotangent lacks are pvaried (type-only, value-preserving). No-op
    when the types already agree.
    """
    ct_vma = leaf_vma(ct)
    p_vma = leaf_vma(primal)
    extra = tuple(sorted(ct_vma - p_vma))
    if extra:
        ct = jax.lax.psum(ct, extra)
    missing = tuple(sorted(p_vma - ct_vma))
    if missing:
        ct = jax.lax.pcast(ct, missing, to="varying")
    return ct


def cast_to_vma(x: jnp.ndarray, vma: frozenset) -> jnp.ndarray:
    """Upcast ``x`` to be device-varying over at least ``vma``
    (idempotent)."""
    cur = leaf_vma(x)
    missing = tuple(a for a in vma if a not in cur)
    if missing:
        x = jax.lax.pcast(x, missing, to="varying")
    return x


def fixed_point_vma(body: Callable, init: Any, x0: Any = None,
                    max_iters: int = 8) -> Any:
    """Per-LEAF varying-axes fixed point for a scan carry.

    ``body(carry, x) -> (carry, ...)``; ``x0`` is a representative first
    scan element (None for a body that ignores ``x``). Returns a pytree of
    frozensets, one per carry leaf — the minimal axes the body actually
    varies each leaf over. Per-leaf minimality matters: a global union
    would over-vary replicated leaves (e.g. tensor-replicated LN grad
    accumulators), breaking replicated out_specs and making AD insert
    spurious cross-replica psums.
    """
    vma_tree = jax.tree_util.tree_map(leaf_vma, init)
    for _ in range(max_iters):
        init_c = jax.tree_util.tree_map(cast_to_vma, init, vma_tree)
        out = jax.eval_shape(lambda c: body(c, x0)[0], init_c)
        new_tree = jax.tree_util.tree_map(
            lambda v, o: v | leaf_vma(o), vma_tree, out)
        if jax.tree_util.tree_all(jax.tree_util.tree_map(
                lambda a, b: a == b, vma_tree, new_tree)):
            break
        vma_tree = new_tree
    return vma_tree


def scan_stable_vma(body: Callable, init: Any, xs: Any, max_iters: int = 4,
                    unroll: Any = 1):
    """``lax.scan`` whose carry VMA is fixed-pointed against the body
    (per-leaf, via :func:`fixed_point_vma`). ``unroll`` passes through to
    ``lax.scan`` (int factor or ``True`` for full unrolling — the form
    whose compiled program XLA's cost analysis can count end to end,
    used by the pyprof attribution validation path)."""
    first_x = jax.tree_util.tree_map(
        lambda v: jax.lax.index_in_dim(v, 0, 0, keepdims=False), xs)
    vma_tree = fixed_point_vma(body, init, first_x, max_iters=max_iters)

    def stable_body(carry, x):
        new_c, y = body(carry, x)
        return jax.tree_util.tree_map(cast_to_vma, new_c, vma_tree), y

    return jax.lax.scan(
        stable_body, jax.tree_util.tree_map(cast_to_vma, init, vma_tree),
        xs, unroll=unroll)


def varying_all_gather(x: jnp.ndarray, axis_name: str, axis: int = 0,
                       tiled: bool = True) -> jnp.ndarray:
    """``lax.all_gather`` with the input pre-cast device-varying — the
    library's single raw-gather chokepoint.

    A replicated-typed value cannot feed ``all_gather`` directly (the op
    demands a varying operand). Every gather outside this module must
    route here (or through :func:`invariant_all_gather`) so the cast
    lives in exactly one place — ``scripts/check_collectives.py`` (wired
    into the test suite) flags raw ``lax.all_gather`` call sites anywhere
    else.
    """
    return jax.lax.all_gather(cast_to_vma(x, frozenset({axis_name})),
                              axis_name, axis=axis, tiled=tiled)


def invariant_all_gather(x: jnp.ndarray, axis_name: str, axis: int = 0
                         ) -> jnp.ndarray:
    """Tiled all-gather typed device-INVARIANT: every rank contributes a
    disjoint slice, so the gathered value is provably replicated and can
    cross ``P()`` out_specs / keep replicated-param AD semantics (a plain
    ``all_gather``'s varying type cannot). ``all_gather_invariant`` has no
    public spelling yet, hence the ``jax._src`` import. Shared by the
    ZeRO param gather and the sequence-parallel gathers."""
    return all_gather_invariant(x, axis_name, axis=axis, tiled=True)

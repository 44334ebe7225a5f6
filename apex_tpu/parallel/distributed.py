"""Data-parallel gradient synchronization.

Reference: ``reference:apex/parallel/distributed.py:129-639`` — a
gradient-hook-driven bucketed NCCL allreduce with comm/compute overlap,
flatten/unflatten copies, predivide factors, and optional fp32 allreduce.

On TPU the *mechanism* disappears: grads live in a jitted step function, the
sync is one ``psum`` per grad tree over the ``data`` mesh axis, and XLA's
latency-hiding scheduler overlaps the collectives with the backward pass
(the hand-built bucket/stream machinery of ``distributed.py:319-556`` is the
compiler's job). What remains semantic — and is kept here — is the numeric
policy: ``gradient_predivide_factor`` (``distributed.py:445-454``: grads are
scaled by ``1/predivide`` before the reduce and ``predivide/world_size``
after, trading overflow headroom in half precision),
``allreduce_always_fp32`` (:168, cast half grads up for the reduce), and
``gradient_average`` (divide by world size or not).

Use inside ``shard_map``/``pmap`` with a named axis, or under jit with
sharding constraints where XLA inserts the psum itself.

**Bucketing** (``bucket_bytes=...``): one psum per grad leaf is the right
default for a handful of large tensors, but a transformer's ~10²–10³ leaves
become that many small latency-bound collectives, while one monolithic
flat psum serializes the whole window behind a single full-tree transfer.
The bucketed path is the reference's bucketed allreduce
(``distributed.py:319-556``; Li et al., VLDB 2021) restated for XLA: grads
are raveled into one flat fp32 vector (the
:mod:`apex_tpu.optimizers._flatten` layout) and reduced in B fixed-size
buckets — B *independent* collectives whose transfers XLA's latency-hiding
scheduler can overlap with each other's scale/unravel epilogues and with
any step work that doesn't consume the synced grads (the loss-scale
update, the local finite-check). The ZeRO optimizers
(:mod:`apex_tpu.optimizers.distributed_fused`) reduce-scatter and
all-gather over the same bucket grid, so bucket k's gather rides under
bucket k+1's update math. This module is also the package's raw
``lax.psum_scatter`` chokepoint (:func:`reduce_scatter_grads`) —
``scripts/check_collectives.py`` flags grad-sync collectives anywhere
else, so future code cannot bypass the bucketing engine.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.observability import health as _health
from apex_tpu.observability import ingraph as _metrics
from apex_tpu.utils.vma import cast_to_vma, leaf_vma
from jax.lax import axis_size as _axis_size

__all__ = ["allreduce_grads", "DistributedDataParallel", "Reducer",
           "grouped_psum", "reduce_scatter_grads", "DEFAULT_BUCKET_BYTES"]

# ~4 MiB per bucket: large enough that per-collective latency amortizes,
# small enough that several buckets are in flight per window (torch-DDP's
# default is 25 MB for NCCL ring allreduce; ICI latencies are lower, so a
# smaller default keeps more overlap opportunity — see docs/PERF.md
# "DP overlap + ZeRO" for the sizing methodology)
DEFAULT_BUCKET_BYTES = 4 << 20


def reduce_scatter_grads(flat: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Tiled fp32 reduce-scatter of a flat grad (bucket) over ``axis_name``
    — each rank receives the *summed* ``1/axis_size`` slice it owns. The
    package's single raw ``lax.psum_scatter`` grad-sync site: ZeRO's
    :meth:`~apex_tpu.optimizers.distributed_fused._DistributedFusedBase.
    _shard_grads` routes here per bucket (``reference:apex/contrib/
    optimizers/distributed_fused_adam.py:409``), and
    ``scripts/check_collectives.py`` flags raw ``psum_scatter`` call sites
    anywhere outside this module (sequence-dim *activation* scatters are
    separately allowlisted there)."""
    return jax.lax.psum_scatter(
        cast_to_vma(flat, frozenset({axis_name})), axis_name,
        scatter_dimension=0, tiled=True)


def grouped_psum(x: jnp.ndarray, axis_name: str,
                 axis_index_groups: Optional[Sequence[Sequence[int]]] = None
                 ) -> jnp.ndarray:
    """``psum`` restricted to device subgroups — the analog of NCCL
    subgroup ``new_group`` communicators
    (``reference:apex/parallel/__init__.py:58+``).

    Resolution order (all paths differentiable, so BN/DDP backward through
    groups works):

    1. native ``psum(axis_index_groups=...)`` — currently raises
       ``NotImplementedError`` inside ``shard_map``; tried first so a
       future JAX picks it up for free;
    2. contiguous equal-size groups (how ``create_syncbn_process_group``
       carves them): ``all_gather`` + a dynamic slice of this rank's group
       + sum — O(world) traffic, O(group) compute;
    3. arbitrary groups: ``all_gather`` + membership-mask contraction —
       O(world) traffic, O(world²·|x|/world) compute; fine for the small
       stat vectors this is used on, wasteful for large tensors at large
       world sizes (documented limitation).
    """
    if axis_index_groups is None:
        return jax.lax.psum(x, axis_name)
    groups = [list(g) for g in axis_index_groups]
    try:
        return jax.lax.psum(x, axis_name, axis_index_groups=groups)
    except NotImplementedError:
        pass
    world = _axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    from apex_tpu.utils.vma import varying_all_gather
    gathered = varying_all_gather(x, axis_name, tiled=False)  # (world, ...)

    sizes = {len(g) for g in groups}
    contiguous_equal = (
        len(sizes) == 1
        and sorted(i for g in groups for i in g) == list(range(world))
        and all(g == list(range(g[0], g[0] + len(g))) for g in groups))
    if contiguous_equal:
        gsize = sizes.pop()
        start = (rank // gsize) * gsize
        mine = jax.lax.dynamic_slice_in_dim(gathered, start, gsize, axis=0)
        return jnp.sum(mine.astype(jnp.float32), axis=0).astype(x.dtype)

    mask = np.zeros((world, world), np.float32)
    for g in groups:
        for i in g:
            for j in g:
                mask[i, j] = 1.0
    row = jnp.asarray(mask)[rank]
    return jnp.tensordot(row, gathered.astype(jnp.float32),
                         axes=1).astype(x.dtype)


def _group_size_for_rank(axis_name: str, groups) -> jnp.ndarray:
    """Traced size of the group containing this rank — groups may be uneven,
    so averaging must use each rank's own group size."""
    world = _axis_size(axis_name)
    sizes = np.zeros((world,), np.float32)
    for g in groups:
        for i in g:
            sizes[i] = len(g)
    return jnp.asarray(sizes)[jax.lax.axis_index(axis_name)]


def _bucketed_allreduce(grads: Any, axis_name: str,
                        gradient_predivide_factor: float,
                        gradient_average: bool, bucket_bytes: int) -> Any:
    """The bucketing engine: ravel the grad tree into B fixed-size flat
    fp32 buckets and psum each (independent collectives XLA can overlap),
    scale per bucket, unravel. Always reduces in fp32 — the ravel *is*
    the fp32 master-grad copy, so ``allreduce_always_fp32`` is implied on
    this path (same numeric contract as the ZeRO reduce-scatter).

    Span-local assembly (``_flatten.ravel_span``/``unravel_parts``): each
    bucket's psum consumes only the grad leaves in its span — not a
    full-tree concatenate — so the scheduler can issue bucket k's
    transfer while the backward is still producing later buckets' grads,
    and each synced leaf is rebuilt from only the buckets covering it.
    The full padded flat vector never materializes (asserted on the
    jaxpr in tests)."""
    from apex_tpu.optimizers._flatten import (bucket_bounds, build_layout,
                                              ravel_span, unravel_parts)
    world = _axis_size(axis_name)
    pre = gradient_predivide_factor
    if gradient_average:
        post = pre / world
    else:
        post = pre if pre != 1.0 else None

    # One bucket grid per varying-axes class. A bucket is a concatenate,
    # and a concatenate of leaves typed over different mesh axes takes
    # the UNION: a tensor-replicated LN grad that shares a bucket with a
    # tensor-sharded weight grad would come back typed tensor-varying,
    # and nothing short of a collective casts that back — the step's
    # replicated out_specs then fail the VMA check. Leaves that agree
    # (every plain-DDP tree; any tree outside shard_map) form ONE class
    # in flatten order, i.e. exactly the single-grid program.
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    classes = {}
    for i, g in enumerate(leaves):
        classes.setdefault(leaf_vma(g), []).append(i)

    synced = [None] * len(leaves)
    total = n_buckets = widest = 0
    with jax.named_scope("apex_ddp_bucketed_allreduce"):
        for idx in classes.values():
            sub = [leaves[i] for i in idx]
            lay = build_layout(sub, chunks=1)
            bounds = bucket_bounds(lay, bucket_bytes)
            total += lay.total
            n_buckets += len(bounds)
            widest = max(widest, max(n for _, n in bounds))
            pieces = []
            for off, n in bounds:
                # one psum per bucket, assembled span-locally: this
                # bucket's transfer depends only on the grads in its
                # span, and the pre/post scales are per-bucket epilogue
                # work the scheduler can run under the next bucket's
                # transfer
                b = ravel_span(sub, lay, off, n)
                if pre != 1.0:
                    b = b / pre
                b = jax.lax.psum(
                    cast_to_vma(b, frozenset({axis_name})), axis_name)
                if post is not None:
                    b = b * post
                pieces.append(b)
            for i, g in zip(idx, unravel_parts(pieces, bounds, lay)):
                synced[i] = g
    synced = jax.tree_util.tree_unflatten(treedef, synced)

    if _metrics.recording():
        _metrics.record("ddp/allreduce_bytes", float(4 * total),
                        reduce="sum")
        _metrics.record("ddp/num_buckets", float(n_buckets), reduce="mean")
        _metrics.record("ddp/bucket_bytes", float(4 * widest),
                        reduce="mean")
    _health.observe_replica_agreement(synced, axis_name, name="ddp_grads")
    return synced


def allreduce_grads(grads: Any, axis_name: str = "data",
                    gradient_predivide_factor: float = 1.0,
                    allreduce_always_fp32: bool = False,
                    gradient_average: bool = True,
                    axis_index_groups: Optional[Sequence[Sequence[int]]] = None,
                    bucket_bytes: Optional[int] = None
                    ) -> Any:
    """psum a grad pytree over ``axis_name`` with apex DDP's numeric options.

    Must be called inside a context where ``axis_name`` is bound
    (``shard_map``, ``pmap``, ...). ``axis_index_groups`` restricts the
    reduction to subgroups — the analog of passing a ``process_group``
    (``reference:apex/parallel/__init__.py:58+``).

    ``bucket_bytes`` switches to the bucketed engine (module docstring):
    the tree is reduced as B flat fp32 buckets instead of one psum per
    leaf — identical numerics to ``allreduce_always_fp32=True`` up to the
    reduction's reassociation, with B independent collectives for XLA's
    scheduler to overlap. ``None`` (default) keeps the per-leaf path
    byte-identical to the pre-bucketing library. Incompatible with
    ``axis_index_groups`` (subgroup reduces stay per-leaf).
    """
    if bucket_bytes is not None:
        if axis_index_groups is not None:
            raise ValueError(
                "bucket_bytes and axis_index_groups are mutually exclusive: "
                "the bucketed engine reduces over the full axis")
        return _bucketed_allreduce(grads, axis_name,
                                   gradient_predivide_factor,
                                   gradient_average, bucket_bytes)
    if axis_index_groups is not None:
        world = _group_size_for_rank(axis_name, axis_index_groups)
    else:
        world = _axis_size(axis_name)
    pre = gradient_predivide_factor

    if _metrics.recording():
        # shapes/dtypes are static, so the reduced traffic is a trace-time
        # constant: this rank's contribution per sync (DDP "bucket" = one
        # leaf = one psum; XLA may coalesce, this counts the semantic view)
        leaves = [jnp.asarray(g) for g in jax.tree_util.tree_leaves(grads)]
        nbytes = sum(
            l.size * (4 if allreduce_always_fp32 else l.dtype.itemsize)
            for l in leaves)
        _metrics.record("ddp/allreduce_bytes", float(nbytes), reduce="sum")
        _metrics.record("ddp/buckets", float(len(leaves)), reduce="mean")

    @jax.named_scope("apex_ddp_allreduce")
    def _sync(g):
        g = jnp.asarray(g)
        orig_dtype = g.dtype
        if allreduce_always_fp32:
            g = g.astype(jnp.float32)
        if pre != 1.0:
            g = g / pre
        g = grouped_psum(g, axis_name, axis_index_groups)
        if gradient_average:
            g = g * (pre / world)
        elif pre != 1.0:
            g = g * pre
        return g.astype(orig_dtype)

    synced = jax.tree_util.tree_map(_sync, grads)
    if axis_index_groups is None:
        # full-level watchdog: post-allreduce grads are replicated by
        # construction, so any cross-replica divergence here is silent
        # corruption (bad collective, bitflip, nondeterministic op) — a
        # trace-time-gated no-op below level="full". Subgroup reduces are
        # exempt: their results legitimately differ across groups.
        _health.observe_replica_agreement(synced, axis_name,
                                          name="ddp_grads")
    return synced


class DistributedDataParallel:
    """Functional DDP: holds the sync policy, applies it to grad trees.

    The ctor keeps the reference's argument names (``distributed.py:162-175``)
    where they still mean something; stream arguments
    (``num_allreduce_streams``, ...) are accepted and ignored — stream
    scheduling is XLA's concern. Bucketing, however, is *real* again:
    ``bucket_bytes`` (the role of the reference's ``message_size``,
    ``distributed.py:165``, restated in bytes) routes :meth:`sync_gradients`
    through the bucketed flat-fp32 engine (module docstring) so the window's
    sync is B overlappable collectives instead of one psum per leaf.

    ``delay_allreduce=True`` is real (torch-DDP ``no_sync`` semantics, the
    closest analog of the reference flag at ``distributed.py:162``):
    :meth:`value_and_grad` then returns *unsynced* per-replica grads so a
    gradient-accumulation loop can sum K microbatches locally and fire
    :meth:`sync_gradients` once per window — see
    :func:`apex_tpu.training.accumulate_gradients`, which packages that
    loop (and whose jaxpr carries exactly one psum per window, asserted in
    tests).
    """

    def __init__(self, axis_name: str = "data",
                 gradient_predivide_factor: float = 1.0,
                 allreduce_always_fp32: bool = False,
                 gradient_average: bool = True,
                 axis_index_groups: Optional[Sequence[Sequence[int]]] = None,
                 delay_allreduce: bool = False,
                 bucket_bytes: Optional[int] = None,
                 **_ignored_stream_args):
        if axis_index_groups is not None and bucket_bytes is not None:
            # same contract as allreduce_grads/Reducer, failed at the
            # misconfiguration site instead of deep inside a later trace
            raise ValueError(
                "bucket_bytes and axis_index_groups are mutually exclusive: "
                "the bucketed engine reduces over the full axis")
        self.axis_name = axis_name
        self.gradient_predivide_factor = gradient_predivide_factor
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.gradient_average = gradient_average
        self.axis_index_groups = axis_index_groups
        self.delay_allreduce = delay_allreduce
        self.bucket_bytes = bucket_bytes

    def sync_gradients(self, grads: Any) -> Any:
        return allreduce_grads(
            grads, self.axis_name, self.gradient_predivide_factor,
            self.allreduce_always_fp32, self.gradient_average,
            self.axis_index_groups, bucket_bytes=self.bucket_bytes)

    def value_and_grad(self, loss_fn, **vag_kwargs):
        """``jax.value_and_grad`` whose grads come back already synced —
        the "wrap your model and backward just works" usage shape of apex DDP.

        The first argument (params) is marked device-varying
        (``lax.pcast(..., to='varying')``) before differentiation: each device differentiates
        its own replica and the sync is this class's explicit allreduce —
        exactly torch-DDP's model. (Without this, shard_map's AD would
        auto-``psum`` cotangents of replicated params and an explicit sync
        would double-count.)

        With ``delay_allreduce=True`` the grads come back UNSYNCED (still
        per-replica, ``no_sync`` semantics) — the caller owns firing
        :meth:`sync_gradients` once per accumulation window.
        """
        def wrapped(params, *args, **kwargs):
            params = jax.tree_util.tree_map(
                lambda p: cast_to_vma(p, frozenset({self.axis_name})), params)
            value, grads = jax.value_and_grad(loss_fn, **vag_kwargs)(
                params, *args, **kwargs)
            if self.delay_allreduce:
                return value, grads
            return value, self.sync_gradients(grads)

        return wrapped


class Reducer:
    """Manual full-reduction helper (``reference:apex/parallel/distributed.py:89-126``):
    no hooks, user calls ``reduce`` explicitly on params or grads; values are
    allreduce-averaged. ``bucket_bytes`` runs the mean through the bucketed
    flat-fp32 engine (B overlappable psums) instead of one pmean per leaf —
    mutually exclusive with ``axis_index_groups`` (the ctor raises, same
    contract as :func:`allreduce_grads`)."""

    def __init__(self, axis_name: str = "data",
                 axis_index_groups: Optional[Sequence[Sequence[int]]] = None,
                 bucket_bytes: Optional[int] = None):
        if axis_index_groups is not None and bucket_bytes is not None:
            raise ValueError(
                "bucket_bytes and axis_index_groups are mutually exclusive: "
                "the bucketed engine reduces over the full axis")
        self.axis_name = axis_name
        self.axis_index_groups = axis_index_groups
        self.bucket_bytes = bucket_bytes

    def reduce(self, tree: Any) -> Any:
        if self.axis_index_groups is not None:
            world = _group_size_for_rank(self.axis_name,
                                         self.axis_index_groups)
            return jax.tree_util.tree_map(
                lambda x: grouped_psum(x, self.axis_name,
                                       self.axis_index_groups) / world,
                tree)
        if self.bucket_bytes is not None:
            return _bucketed_allreduce(tree, self.axis_name, 1.0, True,
                                       self.bucket_bytes)
        return jax.tree_util.tree_map(
            lambda x: jax.lax.pmean(x, self.axis_name), tree)

"""ZeRO-style sharded-optimizer data parallelism.

Reference: ``reference:apex/contrib/optimizers/distributed_fused_adam.py``
(flat grad buffer, ``reduce_scatter`` of grads :409, optimizer state sharded
across the DP group :202-207, ``all_gather`` of updated params :477, comm
overlapped with bprop via grad hooks :162) and ``distributed_fused_lamb.py``
(same scheme + global grad-norm clip + per-tensor trust ratios).

TPU redesign: the whole scheme collapses to three collectives inside
``shard_map`` over the ``data`` mesh axis:

1. grads (replicated layout, one pytree per device) are raveled into one
   flat fp32 vector and ``psum_scatter``'d — each device receives the
   *summed* 1/dp shard it owns, the exact ``reduce_scatter`` of :409;
2. optimizer math (Adam/LAMB, fp32 master params + moments) runs on the
   flat shard only — per-device optimizer state is 1/dp of the dense
   version, the ZeRO memory win;
3. the updated master shard is ``all_gather``'d (:477) and unraveled back
   to the parameter pytree in the parameter dtype.

The reference's manual comm/compute overlap (grad hooks kicking off
reduce-scatters mid-backward, stream pools) is XLA's job here: with the
train step jitted end to end, the latency-hiding scheduler overlaps the
psum_scatter with the tail of the backward. Donate the optimizer state to
avoid the post-backward copy wall.

**Bucketing** (``bucket_bytes=...``): one monolithic reduce-scatter +
all-gather leaves the scheduler nothing to overlap *within* the optimizer
phase — the whole gather waits on the whole update which waits on the
whole scatter. With ``bucket_bytes`` set, the flat vector is carved into
B fixed-size buckets on the shared :func:`~apex_tpu.optimizers._flatten.
bucket_bounds` grid (each a multiple of dp): grads reduce-scatter
per-bucket through the :func:`~apex_tpu.parallel.distributed.
reduce_scatter_grads` chokepoint, Adam's moment/update math runs
per-bucket-shard, and each bucket's updated master all-gathers as soon as
its own math is done — bucket k's gather transfer rides under bucket
k+1's update (and, schedule permitting, under the next step's first
forward, since the gathered params are the only consumers). The master
shard's element order becomes bucket-major (rank slices *within* each
bucket, concatenated) — ``init``/``step``/gather all derive it from the
same static grid, and ``bucket_bytes`` must therefore be identical across
``init`` and every ``step`` (it is a layout property, like dp).
``bucket_bytes=None`` (default) is the single-bucket monolithic path,
numerically and collectively identical to the pre-bucketing module.

Per-tensor quantities (LAMB trust ratios) survive the flat layout via a
static segment-id map from flat index to tensor index (``segment_sum`` on
the shard + ``psum`` = exact per-tensor norms, the role of
``multi_tensor_l2norm`` in ``distributed_fused_lamb.py:435-470``).

``init`` must run inside ``shard_map`` (it slices this rank's shard with
``axis_index``); the natural place is the first jitted train step or an
explicit jitted init step.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.observability import ingraph as _metrics
from apex_tpu.optimizers._base import OptimizerBase, bias_correction
from apex_tpu.optimizers._flatten import (FlatLayout, bucket_bounds,
                                          build_layout, ravel,
                                          ravel_span, segment_ids,
                                          unravel_parts)
from jax.lax import axis_size as _axis_size

__all__ = ["DistributedFusedAdam", "DistributedFusedLAMB",
           "ZeroAdamState", "ZeroLambState"]


# invariant-typed gather shared with the SP/CP layer
from apex_tpu.utils.vma import invariant_all_gather as _all_gather_flat


class ZeroAdamState(NamedTuple):
    step: jnp.ndarray     # i32 scalar
    master: jnp.ndarray   # fp32 flat shard of master params
    exp_avg: jnp.ndarray  # fp32 flat shard
    exp_avg_sq: jnp.ndarray
    # bucket-grid stamp: the bucket_bytes this state's shard layout was
    # built with (0 = monolithic), i32 scalar. The flat shards are
    # bucket-major, so stepping a state under a *different* grid — e.g. a
    # checkpoint trained with one ddp_bucket_bytes restored into a config
    # with another — would silently permute every master/moment element;
    # :meth:`_DistributedFusedBase.check_state` compares this stamp
    # against the optimizer's config wherever the state is concrete (the
    # trainer's jit boundary, eager steps) and fails loudly instead.
    bucket_stamp: Any = 0


# identical layout; one definition so shard-spec plumbing is shared
ZeroLambState = ZeroAdamState


def _cat(parts: list) -> jnp.ndarray:
    """Concat per-bucket pieces; the monolithic single-bucket path skips
    the copy (one definition so the two paths cannot diverge)."""
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


class _DistributedFusedBase(OptimizerBase):
    """Shared flat-shard plumbing, built on the same
    :mod:`apex_tpu.optimizers._flatten` layout helpers as
    :class:`~apex_tpu.optimizers.FlatOptimizer` (``chunks`` = dp here)."""

    def __init__(self, axis_name: str = "data",
                 bucket_bytes: Optional[int] = None):
        self.axis_name = axis_name
        self.bucket_bytes = bucket_bytes
        self._layout: Optional[FlatLayout] = None

    # -- flat layout ------------------------------------------------------
    def _dp(self, lay: FlatLayout) -> int:
        return lay.padded // lay.chunk

    def _layout_for(self, params: Any) -> FlatLayout:
        lay = build_layout(params, chunks=_axis_size(self.axis_name))
        if self._layout is not None and (
                self._layout.shapes != lay.shapes
                or self._layout.chunk != lay.chunk):
            raise ValueError("parameter structure changed between calls")
        self._layout = lay
        return lay

    def _bounds(self, lay: FlatLayout):
        """Global ``(offset, size)`` bucket spans (one span = monolithic)."""
        return bucket_bounds(lay, self.bucket_bytes)

    def _stamp(self) -> jnp.ndarray:
        return jnp.asarray(self.bucket_bytes or 0, jnp.int32)

    def check_state(self, state: Any) -> None:
        """Loud guard for the bucket-grid/state-layout contract: raises
        ``ValueError`` when ``state`` was built under a different
        ``bucket_bytes`` than this optimizer's (the shard order would
        silently permute). A no-op on traced values — call where the
        state is concrete: :meth:`GPTHybridTrainer.jit_train_step` does,
        which is exactly where a restored checkpoint re-enters the step."""
        stamp = getattr(state, "bucket_stamp", None)
        if stamp is None:
            return
        try:
            got = int(stamp)
        except Exception:  # traced: the host-boundary caller owns the check
            return
        expected = int(self.bucket_bytes or 0)
        if got != expected:
            raise ValueError(
                f"ZeRO state was built with bucket_bytes="
                f"{got or None} but this optimizer is configured with "
                f"bucket_bytes={self.bucket_bytes}; the flat shard layout "
                f"is bucket-major, so stepping it would silently permute "
                f"master params and moments. Rebuild the state (init) or "
                f"restore with the matching ddp_bucket_bytes.")

    def _shard_bounds(self, lay: FlatLayout):
        """``(offset, size)`` spans of each bucket's slice *within this
        rank's shard* (the shard is the bucket-major concat of per-bucket
        rank slices)."""
        dp = self._dp(lay)
        out, off = [], 0
        for _goff, n in self._bounds(lay):
            out.append((off, n // dp))
            off += n // dp
        return tuple(out)

    def _my_slice(self, flat: jnp.ndarray, lay: FlatLayout) -> jnp.ndarray:
        """This rank's master shard: its ``1/dp`` slice of every bucket,
        concatenated bucket-major (a single contiguous slice when
        unbucketed)."""
        rank = jax.lax.axis_index(self.axis_name)
        dp = self._dp(lay)
        parts = [
            jax.lax.dynamic_slice_in_dim(flat, off + rank * (n // dp),
                                         n // dp)
            for off, n in self._bounds(lay)]
        return _cat(parts)

    def _shard_grad_parts(self, grads: Any, lay: FlatLayout) -> list:
        """Per-bucket reduce_scatter: flat-averaged grads, this rank's slice
        of each bucket — B independent collectives the scheduler can overlap
        with the per-bucket update math downstream. Each bucket is raveled
        span-locally (``_flatten.ravel_span``): its reduce-scatter consumes
        only the grad leaves in its span, so the scheduler can issue it
        under the tail of the backward (and under the accumulation window)
        as soon as those leaves exist, instead of waiting on a full-tree
        concatenate of every gradient."""
        from apex_tpu.parallel.distributed import reduce_scatter_grads
        bounds = self._bounds(lay)
        if _metrics.recording():
            _metrics.record("ddp/reduce_scatter_bytes",
                            float(4 * lay.padded), reduce="sum")
            _metrics.record("zero/shard_bytes", float(4 * lay.chunk),
                            reduce="mean")
            if self.bucket_bytes is not None:
                # the bucket-grid metrics are the bucketed path's contract
                # (docs/OBSERVABILITY.md) — a monolithic ZeRO step must
                # not report a degenerate 1-bucket grid as bucketing-on
                _metrics.record("ddp/num_buckets", float(len(bounds)),
                                reduce="mean")
                _metrics.record("ddp/bucket_bytes",
                                float(4 * max(n for _, n in bounds)),
                                reduce="mean")
        inv_dp = 1.0 / self._dp(lay)
        return [
            reduce_scatter_grads(
                ravel_span(grads, lay, off, n),
                self.axis_name) * inv_dp
            for off, n in bounds]

    def _shard_grads(self, grads: Any, lay: FlatLayout) -> jnp.ndarray:
        """reduce_scatter: flat-averaged grads, this rank's shard only."""
        return _cat(self._shard_grad_parts(grads, lay))

    def _gather_master_parts(self, parts: list, lay: FlatLayout) -> list:
        """Per-bucket all-gather of updated master slices back to
        per-bucket full spans. Each bucket's gather depends only on that
        bucket's update, so it can start while later buckets are still in
        their math — and downstream, each parameter leaf is unraveled
        from only its own buckets (:meth:`_unravel_parts_like`), so the
        full flat vector is never concatenated back together."""
        return [_all_gather_flat(p, self.axis_name, axis=0) for p in parts]

    def _unravel_parts_like(self, parts: list, lay: FlatLayout,
                            like: Any = None) -> Any:
        """Per-bucket inverse of ravel: ``parts[i]`` covers the i-th
        bucket span; each leaf is assembled from only the parts covering
        it — parameter leaf j is ready as soon as its own buckets'
        gathers land, not after every bucket's."""
        new_params = unravel_parts(parts, self._bounds(lay), lay)
        if like is None:
            return new_params
        # the flat master mixes leaves with different varying-axes sets, so
        # every unraveled leaf inherits the union (e.g. an LN weight comes
        # back typed tensor-varying next to TP-sharded leaves). Replicated-
        # by-construction leaves are value-identical across those extra
        # axes, so a pmean is a value identity that restores each leaf's
        # original type (required by the caller's out_specs).

        from apex_tpu.utils.vma import leaf_vma

        def rec(n, p):
            extra = leaf_vma(n) - leaf_vma(p)
            if extra:
                n = jax.lax.pmean(n, tuple(sorted(extra)))
            return n

        return jax.tree_util.tree_map(rec, new_params, like)

    def _gather_params(self, master: jnp.ndarray, lay: FlatLayout,
                       like: Any = None) -> Any:
        """all_gather of a whole updated master shard (per-bucket under the
        hood) and unravel back to the parameter pytree."""
        parts = [master[o:o + n] for o, n in self._shard_bounds(lay)]
        return self._unravel_parts_like(
            self._gather_master_parts(parts, lay), lay, like)


class DistributedFusedAdam(_DistributedFusedBase):
    """ZeRO sharded Adam/AdamW (``distributed_fused_adam.py:9``).

    Numerics match :class:`apex_tpu.optimizers.FusedAdam` with DDP grad
    averaging, while per-device optimizer state (fp32 master + m + v) is
    1/dp of the dense version.
    """

    def __init__(self, lr: float = 1e-3, bias_correction: bool = True,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-8,
                 adam_w_mode: bool = True, weight_decay: float = 0.0,
                 axis_name: str = "data",
                 bucket_bytes: Optional[int] = None):
        super().__init__(axis_name, bucket_bytes=bucket_bytes)
        self.lr = lr
        self.use_bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.adam_w_mode = adam_w_mode
        self.weight_decay = weight_decay

    def init(self, params: Any) -> ZeroAdamState:
        lay = self._layout_for(params)
        master = self._my_slice(ravel(params, lay), lay)
        zeros = jnp.zeros(lay.chunk, jnp.float32)
        return ZeroAdamState(step=jnp.asarray(0, jnp.int32), master=master,
                             exp_avg=zeros, exp_avg_sq=zeros,
                             bucket_stamp=self._stamp())

    def _step(self, grads: Any, state: ZeroAdamState, params: Any,
              lr: Optional[Any] = None,
              weight_decay: Optional[Any] = None
              ) -> Tuple[Any, ZeroAdamState]:
        self.check_state(state)  # loud on eager grid mismatch; traced no-op
        lay = self._layout_for(params)
        lr = jnp.asarray(self.lr if lr is None else lr, jnp.float32)
        wd = jnp.asarray(
            self.weight_decay if weight_decay is None else weight_decay,
            jnp.float32)
        t = state.step + 1
        if self.use_bias_correction:
            bc1 = bias_correction(self.beta1, t)
            bc2 = bias_correction(self.beta2, t)
        else:
            bc1 = bc2 = jnp.asarray(1.0, jnp.float32)
        b1, b2 = self.beta1, self.beta2

        # Per-bucket pipeline: bucket b's chain is
        #   reduce_scatter(b) -> moment/update math(b) -> all_gather(b)
        # with no cross-bucket dependencies AND no full-tree joins on
        # either end (span-local ravel in, per-bucket unravel out), so
        # XLA's latency-hiding scheduler can issue bucket k's scatter
        # under the backward tail the moment its grads exist, run bucket
        # k's gather transfer under bucket k+1's scatter + math, and hand
        # each layer its updated params as soon as that layer's buckets
        # land. Unbucketed this degenerates to the original single-chain
        # program.
        g_parts = self._shard_grad_parts(grads, lay)
        sbounds = self._shard_bounds(lay)
        ms, vs, masters, gathered = [], [], [], []
        for g, (o, n) in zip(g_parts, sbounds):
            p32 = state.master[o:o + n]
            if not self.adam_w_mode:
                g = g + wd * p32
            m = b1 * state.exp_avg[o:o + n] + (1.0 - b1) * g
            v = b2 * state.exp_avg_sq[o:o + n] + (1.0 - b2) * g * g
            update = (m / bc1) / (jnp.sqrt(v / bc2) + self.eps)
            if self.adam_w_mode:
                update = update + wd * p32
            new_master = p32 - lr * update
            ms.append(m)
            vs.append(v)
            masters.append(new_master)
            gathered.append(_all_gather_flat(new_master, self.axis_name,
                                             axis=0))
        new_params = self._unravel_parts_like(gathered, lay, like=params)
        return new_params, ZeroAdamState(
            step=t, master=_cat(masters), exp_avg=_cat(ms),
            exp_avg_sq=_cat(vs), bucket_stamp=state.bucket_stamp)


class DistributedFusedLAMB(_DistributedFusedBase):
    """ZeRO sharded LAMB (``distributed_fused_lamb.py:10``): global grad-norm
    clip, then per-tensor trust ratios — per-tensor norms come from
    ``segment_sum`` on the flat shard + ``psum`` (exact, not approximated).

    With ``bucket_bytes`` the reduce-scatter and param all-gather are
    per-bucket like Adam's, but the update math stays whole-shard: the
    global clip and cross-shard trust-ratio psums are barriers every
    bucket's update depends on, so a per-bucket math pipeline would buy
    nothing (the overlap win here is scatter-vs-backward and
    gather-vs-unravel only).
    """

    def __init__(self, lr: float = 1e-3, bias_correction: bool = True,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-6,
                 weight_decay: float = 0.01, max_grad_norm: float = 1.0,
                 use_nvlamb: bool = False, axis_name: str = "data",
                 bucket_bytes: Optional[int] = None):
        super().__init__(axis_name, bucket_bytes=bucket_bytes)
        self.lr = lr
        self.use_bias_correction = bias_correction
        self.beta1, self.beta2 = betas
        self.eps = eps
        self.weight_decay = weight_decay
        self.max_grad_norm = max_grad_norm
        self.use_nvlamb = use_nvlamb

    def init(self, params: Any) -> ZeroLambState:
        lay = self._layout_for(params)
        master = self._my_slice(ravel(params, lay), lay)
        zeros = jnp.zeros(lay.chunk, jnp.float32)
        return ZeroLambState(step=jnp.asarray(0, jnp.int32), master=master,
                             exp_avg=zeros, exp_avg_sq=zeros,
                             bucket_stamp=self._stamp())

    def _per_tensor(self, vec_sq: jnp.ndarray, seg: jnp.ndarray,
                    lay: FlatLayout) -> jnp.ndarray:
        """psum of shard-local segment sums -> per-tensor sums (n_tensors+1,
        last slot is padding). Routed through the distributed.py psum
        chokepoint (scripts/check_collectives.py bans raw grad-path psums
        in this package); imported lazily — apex_tpu.parallel's __init__
        imports the optimizers package back."""
        from apex_tpu.parallel.distributed import grouped_psum
        part = jax.ops.segment_sum(vec_sq, seg, num_segments=len(lay.sizes) + 1)
        return grouped_psum(part, self.axis_name)

    def _step(self, grads: Any, state: ZeroLambState, params: Any,
              lr: Optional[Any] = None,
              weight_decay: Optional[Any] = None
              ) -> Tuple[Any, ZeroLambState]:
        self.check_state(state)  # loud on eager grid mismatch; traced no-op
        lay = self._layout_for(params)
        lr = jnp.asarray(self.lr if lr is None else lr, jnp.float32)
        wd = jnp.asarray(
            self.weight_decay if weight_decay is None else weight_decay,
            jnp.float32)
        t = state.step + 1
        if self.use_bias_correction:
            bc1 = bias_correction(self.beta1, t)
            bc2 = bias_correction(self.beta2, t)
        else:
            bc1 = bc2 = jnp.asarray(1.0, jnp.float32)
        b1, b2 = self.beta1, self.beta2
        seg = self._my_slice(segment_ids(lay), lay)

        from apex_tpu.parallel.distributed import grouped_psum
        g = self._shard_grads(grads, lay)
        # phase 1: global grad-norm clip (reference fused_lamb.py:124-152)
        gnorm_sq = grouped_psum(jnp.sum(g * g), self.axis_name)
        gnorm = jnp.sqrt(gnorm_sq)
        clip = jnp.where(
            (self.max_grad_norm > 0) & (gnorm > self.max_grad_norm),
            gnorm / self.max_grad_norm, 1.0)
        g = g / clip

        p32 = state.master
        m = b1 * state.exp_avg + (1.0 - b1) * g
        v = b2 * state.exp_avg_sq + (1.0 - b2) * g * g
        update = (m / bc1) / (jnp.sqrt(v / bc2) + self.eps) + wd * p32

        # phase 2: per-tensor trust ratios
        p_norm = jnp.sqrt(self._per_tensor(p32 * p32, seg, lay))
        u_norm = jnp.sqrt(self._per_tensor(update * update, seg, lay))
        if self.use_nvlamb:
            ratio = jnp.where(u_norm > 0, p_norm / u_norm, 1.0)
        else:
            ratio = jnp.where((p_norm > 0) & (u_norm > 0),
                              p_norm / u_norm, 1.0)
        new_master = p32 - lr * jnp.take(ratio, seg) * update
        new_params = self._gather_params(new_master, lay, like=params)
        return new_params, ZeroLambState(step=t, master=new_master,
                                         exp_avg=m, exp_avg_sq=v,
                                         bucket_stamp=state.bucket_stamp)

"""ZeRO sharded-optimizer tests (``reference:apex/contrib/test/optimizers/
test_dist_adam.py`` role): numeric parity with the dense optimizer + DDP,
and the 1/dp state-memory property that is ZeRO's point.

Runs on the 8-virtual-CPU-device mesh from conftest.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.amp.scaler import all_finite
from apex_tpu.optimizers import (
    DistributedFusedAdam, DistributedFusedLAMB, FusedAdam, FusedLAMB,
    ZeroAdamState, ZeroLambState)

DP = 4


def _state_spec(opt):
    cls = ZeroAdamState if isinstance(opt, DistributedFusedAdam) \
        else ZeroLambState
    return cls(step=P(), master=P("data"), exp_avg=P("data"),
               exp_avg_sq=P("data"), bucket_stamp=P())


@pytest.fixture
def mesh():
    return Mesh(np.array(jax.devices()[:DP]), ("data",))


def _params(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(rng.randn(16, 33), jnp.float32),  # odd size: padding
        "b": jnp.asarray(rng.randn(33), jnp.float32),
        "emb": jnp.asarray(rng.randn(7, 16), jnp.float32),
    }


def _per_rank_grads(params, seed=1):
    """One distinct grad pytree per DP rank, stacked on axis 0."""
    rng = np.random.RandomState(seed)
    return jax.tree_util.tree_map(
        lambda p: jnp.asarray(rng.randn(DP, *np.shape(p)), jnp.float32), params)


def _run_zero(mesh, opt, params, grads_stacked, n_steps, grads_finite=None):
    """Jitted shard_map step loop: grads sharded over data (one replica's
    grads per device), params replicated in/out."""

    def stepper(params, grads_stacked):
        def inner(params, grads_stacked):
            state = opt.init(params)
            for i in range(n_steps):
                g = jax.tree_util.tree_map(lambda s: s[0], grads_stacked)
                params, state = opt.step(g, state, params,
                                         grads_finite=grads_finite)
            return params, state
        gspec = jax.tree_util.tree_map(lambda _: P("data"), grads_stacked)
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(), gspec),
                         out_specs=(P(), _state_spec(opt)))(
                             params, grads_stacked)

    return jax.jit(stepper)(params, grads_stacked)


def _run_dense(opt, params, grads_stacked, n_steps):
    """Dense reference: DDP grad averaging is a plain mean over ranks."""
    state = opt.init(params)
    for _ in range(n_steps):
        g = jax.tree_util.tree_map(lambda s: jnp.mean(s, 0), grads_stacked)
        params, state = opt.step(g, state, params)
    return params, state


def test_zero_adam_matches_dense_ddp(mesh):
    params = _params()
    grads = _per_rank_grads(params)
    kw = dict(lr=1e-2, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.01)
    zp, zstate = _run_zero(mesh, DistributedFusedAdam(**kw), params, grads, 3)
    dp_, _ = _run_dense(FusedAdam(**kw), params, grads, 3)
    for k in params:
        np.testing.assert_allclose(np.asarray(zp[k]), np.asarray(dp_[k]),
                                   rtol=2e-6, atol=2e-6)


def test_zero_adam_l2_mode(mesh):
    params = _params(2)
    grads = _per_rank_grads(params, 3)
    kw = dict(lr=1e-2, adam_w_mode=False, weight_decay=0.1)
    zp, _ = _run_zero(mesh, DistributedFusedAdam(**kw), params, grads, 2)
    dp_, _ = _run_dense(FusedAdam(**kw), params, grads, 2)
    for k in params:
        np.testing.assert_allclose(np.asarray(zp[k]), np.asarray(dp_[k]),
                                   rtol=2e-6, atol=2e-6)


def test_zero_lamb_matches_dense_ddp(mesh):
    params = _params(4)
    grads = _per_rank_grads(params, 5)
    kw = dict(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0)
    zp, _ = _run_zero(mesh, DistributedFusedLAMB(**kw), params, grads, 3)
    dp_, _ = _run_dense(FusedLAMB(**kw), params, grads, 3)
    for k in params:
        np.testing.assert_allclose(np.asarray(zp[k]), np.asarray(dp_[k]),
                                   rtol=2e-5, atol=2e-5)


def test_zero_state_is_sharded(mesh):
    """Per-device optimizer state is 1/dp of the dense state — the ZeRO
    memory win (reference distributed_fused_adam.py:202-207)."""
    params = _params()
    grads = _per_rank_grads(params)
    total = sum(int(np.prod(np.shape(p))) for p in
                jax.tree_util.tree_leaves(params))
    padded = ((total + DP - 1) // DP) * DP

    _, zstate = _run_zero(mesh, DistributedFusedAdam(lr=1e-3), params,
                          grads, 1)
    # out_specs P("data") stacks per-rank shards: global (dp*shard,), and
    # each device's addressable shard is padded/dp
    for leaf in (zstate.master, zstate.exp_avg, zstate.exp_avg_sq):
        assert leaf.shape == (padded,)
        assert leaf.addressable_shards[0].data.shape == (padded // DP,)


def test_zero_overflow_skip(mesh):
    params = _params()
    grads = jax.tree_util.tree_map(
        lambda p: jnp.full((DP, *np.shape(p)), jnp.inf, jnp.float32), params)
    finite = all_finite(grads)
    zp, zstate = _run_zero(mesh, DistributedFusedAdam(lr=1e-2), params,
                           grads, 1, grads_finite=finite)
    for k in params:
        np.testing.assert_array_equal(np.asarray(zp[k]), np.asarray(params[k]))
    assert int(zstate.step) == 0  # step count did not advance


def test_zero_bf16_params_fp32_master(mesh):
    """bf16 params train through an fp32 master shard: the update applied at
    fp32 precision survives the roundtrip (amp O2 semantics)."""
    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.bfloat16), _params(6))
    grads = jax.tree_util.tree_map(
        lambda g: g.astype(jnp.float32), _per_rank_grads(params, 7))
    zp, zstate = _run_zero(mesh, DistributedFusedAdam(lr=1e-3), params,
                           grads, 2)
    for k in params:
        assert zp[k].dtype == jnp.bfloat16
    # master is fp32 and differs from the bf16 roundtrip by < 1 bf16 ulp
    assert zstate.master.dtype == jnp.float32


def test_zero_bucketed_matches_dense_ddp(mesh):
    """Per-bucket reduce-scatter/all-gather (bucket_bytes) keeps exact
    parity with the dense optimizer + DDP mean: the bucket grid only
    re-partitions the flat vector, every element sees the same fp32
    arithmetic (the reduction order inside each collective is the
    backend's, same as unbucketed)."""
    params = _params(8)
    grads = _per_rank_grads(params, 9)
    kw = dict(lr=1e-2, betas=(0.9, 0.95), eps=1e-8, weight_decay=0.01)
    dp_, _ = _run_dense(FusedAdam(**kw), params, grads, 3)
    for bb in (256, 4096):
        zp, _ = _run_zero(mesh, DistributedFusedAdam(**kw, bucket_bytes=bb),
                          params, grads, 3)
        for k in params:
            np.testing.assert_allclose(np.asarray(zp[k]), np.asarray(dp_[k]),
                                       rtol=2e-6, atol=2e-6)
    # LAMB: bucketed scatter/gather around the whole-shard trust-ratio math
    kwl = dict(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0)
    dl, _ = _run_dense(FusedLAMB(**kwl), params, grads, 2)
    zl, _ = _run_zero(mesh, DistributedFusedLAMB(**kwl, bucket_bytes=256),
                      params, grads, 2)
    for k in params:
        np.testing.assert_allclose(np.asarray(zl[k]), np.asarray(dl[k]),
                                   rtol=2e-5, atol=2e-5)


def test_zero_bucketed_state_is_sharded(mesh):
    """Bucketing re-orders the master shard (bucket-major) but never its
    size: per-device state stays padded/dp — the ZeRO memory win is
    bucket-size-independent."""
    from apex_tpu.optimizers._flatten import bucket_bounds, build_layout

    params = _params()
    grads = _per_rank_grads(params)
    total = sum(int(np.prod(np.shape(p))) for p in
                jax.tree_util.tree_leaves(params))
    padded = ((total + DP - 1) // DP) * DP
    opt = DistributedFusedAdam(lr=1e-3, bucket_bytes=256)
    _, zstate = _run_zero(mesh, opt, params, grads, 1)
    assert len(bucket_bounds(build_layout(params, chunks=DP), 256)) > 1
    for leaf in (zstate.master, zstate.exp_avg, zstate.exp_avg_sq):
        assert leaf.shape == (padded,)
        assert leaf.addressable_shards[0].data.shape == (padded // DP,)


def test_zero_bucketed_jaxpr_per_bucket_collectives(mesh):
    """B buckets -> exactly B data-axis reduce-scatters and B gathers in
    the step jaxpr (counted structurally; the gather is B invariant
    all-gathers where this jax has them, else B bucket-sized psums via the
    documented fallback)."""
    from _jaxpr_utils import count_eqns, eqn_axes
    from apex_tpu.optimizers._flatten import bucket_bounds, build_layout

    bb = 256
    opt = DistributedFusedAdam(lr=1e-2, bucket_bytes=bb)
    params = _params()
    grads = jax.tree_util.tree_map(jnp.ones_like, params)
    lay = build_layout(params, chunks=DP)
    bounds = bucket_bounds(lay, bb)
    B = len(bounds)
    assert B > 1

    def step(params, grads):
        def inner(params, grads):
            state = opt.init(params)
            return opt.step(grads, state, params)[0]
        gspec = jax.tree_util.tree_map(lambda _: P(), grads)
        return shard_map(inner, mesh=mesh, in_specs=(P(), gspec),
                         out_specs=P())(params, grads)

    jaxpr = jax.make_jaxpr(step)(params, grads)

    def on_data(eqn):
        return "data" in eqn_axes(eqn)

    assert count_eqns(jaxpr, "reduce_scatter", where=on_data) == B
    sizes = {n for _, n in bounds}
    n_ag = (count_eqns(jaxpr, "all_gather", where=on_data)
            + count_eqns(jaxpr, "all_gather_invariant", where=on_data))

    def psums(where):
        # 0.4.x check_rep shard_map rewrites psum to its psum2 variant
        return (count_eqns(jaxpr, "psum", where=where)
                + count_eqns(jaxpr, "psum2", where=where))

    n_fallback = psums(lambda e: on_data(e) and any(
        v.aval.ndim == 1 and v.aval.size in sizes for v in e.invars))
    assert n_ag == B or n_fallback >= B, (n_ag, n_fallback, B)
    # and never a monolithic reduction of the whole padded flat vector
    full = lambda e: on_data(e) and any(
        v.aval.ndim == 1 and v.aval.size == lay.padded for v in e.invars)
    assert psums(full) == 0
    assert count_eqns(jaxpr, "reduce_scatter", where=full) == (
        0 if B > 1 else 1)


def test_zero_bucket_grid_is_value_transparent(mesh):
    """bucket_bytes is a layout-internal property (it re-orders the master
    shard bucket-major but changes no values): bucketed and unbucketed
    optimizers produce the same parameter updates. The grid must be
    identical across init and step — guaranteed by construction, since the
    same opt object carries it (docstring contract)."""
    params = _params()
    grads = _per_rank_grads(params)
    kw = dict(lr=1e-2)
    zp_a, _ = _run_zero(mesh, DistributedFusedAdam(**kw, bucket_bytes=256),
                        params, grads, 1)
    zp_b, _ = _run_zero(mesh, DistributedFusedAdam(**kw), params, grads, 1)
    # different grids, same update values — the grid is layout-internal
    for k in params:
        np.testing.assert_allclose(np.asarray(zp_a[k]), np.asarray(zp_b[k]),
                                   rtol=2e-6, atol=2e-6)


def test_zero_bucket_grid_mismatch_is_loud(mesh):
    """A state built under one bucket grid must not be stepped under
    another — the shard order is bucket-major, so the mismatch would
    silently permute master params. check_state (and the eager _step)
    raises instead; the stamp round-trips through a save/restore since it
    is an ordinary state leaf."""
    params = _params()
    grads = _per_rank_grads(params)
    _, state = _run_zero(mesh, DistributedFusedAdam(lr=1e-2), params,
                         grads, 1)
    assert int(state.bucket_stamp) == 0  # monolithic stamp
    mismatched = DistributedFusedAdam(lr=1e-2, bucket_bytes=256)
    with pytest.raises(ValueError, match="bucket-major|bucket_bytes"):
        mismatched.check_state(state)
    # matching config passes
    DistributedFusedAdam(lr=1e-2).check_state(state)
    _, state_b = _run_zero(mesh, mismatched, params, grads, 1)
    assert int(state_b.bucket_stamp) == 256
    mismatched.check_state(state_b)


def test_zero_step_compiles_to_three_collectives(mesh):
    """The module docstring's performance story: the whole ZeRO step is
    psum_scatter(grads) + [LAMB-only psums] + one all-gather of updated
    params — no hidden extra all-reduces. Counted in the compiled HLO
    (overlap itself is XLA's latency-hiding scheduler; the countable
    invariant is that there is nothing else to overlap-hide)."""
    try:
        from jax._src.lax.parallel import all_gather_invariant  # noqa: F401
    except ImportError:
        pytest.skip("this jax lacks all_gather_invariant; the param "
                    "gather lowers via the documented psum fallback, so "
                    "the 3-collective pattern doesn't apply")
    opt = DistributedFusedAdam(lr=1e-2)
    params = _params()
    grads = jax.tree_util.tree_map(jnp.ones_like, params)

    def step(params, grads):
        def inner(params, grads):
            state = opt.init(params)
            return opt.step(grads, state, params)[0]
        gspec = jax.tree_util.tree_map(lambda _: P(), grads)
        return shard_map(inner, mesh=mesh, in_specs=(P(), gspec),
                         out_specs=P())(params, grads)

    txt = jax.jit(step).lower(params, grads).compile().as_text()
    n_rs = txt.count("reduce-scatter(")
    n_ag = txt.count("all-gather(") + txt.count("all-gather-start(")
    n_ar = txt.count("all-reduce(") + txt.count("all-reduce-start(")
    assert n_rs == 1, txt.count("reduce-scatter")
    assert n_ag == 1
    assert n_ar == 0

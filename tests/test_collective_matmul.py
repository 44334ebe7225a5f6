"""Ring-decomposed collective matmul (tensor_parallel/collective_matmul).

The acceptance contract of the overlap work (ISSUE 2): the decomposed path
must be numerically interchangeable with the fused collectives — bit-exact
at TP=2, where the two-term fp32 ring sum is commutative — and its jaxpr
must actually BE decomposed: ``tp−1`` ppermutes per ring and no
``all_gather``/``reduce_scatter`` (psum_scatter's primitive name) for the
wired layers. Correctness runs on the CPU mesh; the speedup is measured on
TPU by ``bench.py::bench_gpt_sp_overlap``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.tensor_parallel import (
    ColumnParallelLinear, RowParallelLinear, all_gather_matmul,
    matmul_reduce_scatter)
from jax import shard_map


@pytest.fixture(params=[2, 4])
def mesh_tp(request):
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=request.param)
    yield mesh, request.param
    parallel_state.destroy_model_parallel()


@pytest.fixture
def mesh_tp2():
    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2)
    yield mesh
    parallel_state.destroy_model_parallel()


# ---------------------------------------------------------------------------
# primitive-level: values and grads vs the fused reference
# ---------------------------------------------------------------------------

def test_all_gather_matmul_matches_fused(mesh_tp):
    mesh, tp = mesh_tp
    rng = np.random.RandomState(0)
    b, s, din, dout = 2, 8, 8, 8
    x = jnp.asarray(rng.randn(b, s, din), jnp.float32)
    w = jnp.asarray(rng.randn(tp, dout // tp, din), jnp.float32)

    def ring(x, w):
        def inner(x, w):
            return all_gather_matmul(x, w[0], "tensor", 1)
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, "tensor", None), P("tensor")),
                         out_specs=P(None, None, "tensor"))(x, w)

    def fused(x, w):
        def inner(x, w):
            xg = jax.lax.all_gather(x, "tensor", axis=1, tiled=True)
            return jax.lax.dot_general(
                xg, w[0], (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, "tensor", None), P("tensor")),
                         out_specs=P(None, None, "tensor"))(x, w)

    y_ring = jax.jit(ring)(x, w)
    y_fused = jax.jit(fused)(x, w)
    # seq chunking never changes a row's contraction: bit-identical at any tp
    np.testing.assert_array_equal(np.asarray(y_ring), np.asarray(y_fused))

    # grads vs the dense TP=1 reference
    def loss_ring(x, w):
        def inner(x, w):
            y = all_gather_matmul(x, w[0], "tensor", 1)
            return jax.lax.psum(jnp.sum(y * y), "tensor")
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, "tensor", None), P("tensor")),
                         out_specs=P())(x, w)

    gx, gw = jax.jit(jax.grad(loss_ring, argnums=(0, 1)))(x, w)
    wfull = jnp.asarray(np.asarray(w).reshape(dout, din))

    def loss_dense(x, wfull):
        y = x @ wfull.T
        return jnp.sum(y * y)

    gxr, gwr = jax.grad(loss_dense, argnums=(0, 1))(x, wfull)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gxr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(gw).reshape(dout, din),
                               np.asarray(gwr), rtol=1e-5, atol=1e-5)


def test_matmul_reduce_scatter_matches_fused(mesh_tp):
    mesh, tp = mesh_tp
    rng = np.random.RandomState(1)
    b, s, din, dout = 2, 8, 8, 8
    x = jnp.asarray(rng.randn(b, s, din), jnp.float32)
    w = jnp.asarray(rng.randn(tp, dout, din // tp), jnp.float32)
    add = jnp.asarray(rng.randn(dout), jnp.float32)

    def ring(x, w, add):
        def inner(x, w, add):
            return matmul_reduce_scatter(x, w[0], add, "tensor", 1)
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, None, "tensor"), P("tensor"),
                                   P()),
                         out_specs=P(None, "tensor", None))(x, w, add)

    def fused(x, w, add):
        def inner(x, w, add):
            part = jax.lax.dot_general(
                x, w[0], (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) + add
            return jax.lax.psum_scatter(part, "tensor",
                                        scatter_dimension=1, tiled=True)
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, None, "tensor"), P("tensor"),
                                   P()),
                         out_specs=P(None, "tensor", None))(x, w, add)

    y_ring = jax.jit(ring)(x, w, add)
    y_fused = jax.jit(fused)(x, w, add)
    if tp == 2:
        # two-term fp32 sums are commutative: ring order == psum order
        np.testing.assert_array_equal(np.asarray(y_ring),
                                      np.asarray(y_fused))
    else:
        # documented <=1-ULP-class fp32 reassociation beyond tp=2
        np.testing.assert_allclose(np.asarray(y_ring), np.asarray(y_fused),
                                   rtol=1e-6, atol=1e-6)

    # grads vs the dense reference (each rank's partial carries `add`,
    # so the dense model sees tp*add)
    def loss_ring(x, w, add):
        def inner(x, w, add):
            y = matmul_reduce_scatter(x, w[0], add, "tensor", 1)
            return jax.lax.psum(jnp.sum(y * y), "tensor")
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, None, "tensor"), P("tensor"),
                                   P()),
                         out_specs=P())(x, w, add)

    gx, gw, ga = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(x, w, add)
    wfull = jnp.asarray(np.concatenate(list(np.asarray(w)), axis=1))

    def loss_dense(x, wfull, add):
        y = x @ wfull.T + tp * add
        return jnp.sum(y * y)

    gxr, gwr, gar = jax.grad(loss_dense, argnums=(0, 1, 2))(x, wfull, add)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gxr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.concatenate(list(np.asarray(gw)), axis=1), np.asarray(gwr),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gar),
                               rtol=1e-5, atol=1e-4)


def test_matmul_reduce_scatter_scalar_partial_add_grad(mesh_tp2):
    """partial_add is '(out,)-broadcastable': the backward's
    broadcast-transpose must also handle a scalar (sum every axis)."""
    mesh = mesh_tp2
    rng = np.random.RandomState(7)
    tp, b, s, din, dout = 2, 2, 4, 4, 4
    x = jnp.asarray(rng.randn(b, s, din), jnp.float32)
    w = jnp.asarray(rng.randn(tp, dout, din // tp), jnp.float32)
    add = jnp.float32(0.5)

    def loss(x, w, add):
        def inner(x, w, add):
            y = matmul_reduce_scatter(x, w[0], add, "tensor", 1)
            return jax.lax.psum(jnp.sum(y * y), "tensor")
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, None, "tensor"), P("tensor"),
                                   P()),
                         out_specs=P())(x, w, add)

    ga = jax.jit(jax.grad(loss, argnums=2))(x, w, add)
    wfull = jnp.asarray(np.concatenate(list(np.asarray(w)), axis=1))
    gar = jax.grad(
        lambda x, wf, a: jnp.sum((x @ wf.T + tp * a) ** 2),
        argnums=2)(x, wfull, add)
    np.testing.assert_allclose(float(ga), float(gar), rtol=1e-5)


def test_matmul_reduce_scatter_rejects_indivisible_seq(mesh_tp2):
    mesh = mesh_tp2
    x = jnp.ones((2, 7, 4))  # 7 % 2 != 0
    w = jnp.ones((2, 4, 2))

    def run(x, w):
        def inner(x, w):
            return matmul_reduce_scatter(x, w[0], None, "tensor", 1)
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, None, "tensor"), P("tensor")),
                         out_specs=P(None, "tensor", None))(x, w)

    with pytest.raises(ValueError, match="not divisible"):
        jax.jit(run)(x, w)


# ---------------------------------------------------------------------------
# jaxpr shape: the decomposition is real (acceptance criterion)
# ---------------------------------------------------------------------------

from _jaxpr_utils import collective_census as _census  # noqa: E402


def test_jaxpr_ring_decomposition_primitives(mesh_tp):
    mesh, tp = mesh_tp
    x = jnp.ones((2, 8, 8), jnp.float32)
    w = jnp.ones((tp, 8 // tp, 8), jnp.float32)

    def fwd(x, w):
        def inner(x, w):
            return all_gather_matmul(x, w[0], "tensor", 1)
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, "tensor", None), P("tensor")),
                         out_specs=P(None, None, "tensor"))(x, w)

    c = _census(jax.make_jaxpr(fwd)(x, w))
    assert c == {"ppermute": tp - 1, "all_gather": 0, "reduce_scatter": 0}

    # fwd+bwd: the backward ring (RS of dX) adds its own tp-1 ppermutes
    def loss(x, w):
        def inner(x, w):
            y = all_gather_matmul(x, w[0], "tensor", 1)
            return jax.lax.psum(jnp.sum(y * y), "tensor")
        return shard_map(inner, mesh=mesh,
                         in_specs=(P(None, "tensor", None), P("tensor")),
                         out_specs=P())(x, w)

    c = _census(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w))
    assert c == {"ppermute": 2 * (tp - 1), "all_gather": 0,
                 "reduce_scatter": 0}


def test_jaxpr_ring_decomposition_wired_layers(mesh_tp2):
    """The SP-wired Column+Row pair, overlap on: fwd+bwd jaxpr holds
    exactly the ring ppermutes (4 rings x (tp-1)) and ZERO fused
    all-gathers/reduce-scatters — the collectives really were replaced,
    not supplemented."""
    mesh = mesh_tp2
    tp, b, s, h = 2, 2, 8, 8
    col = ColumnParallelLinear(h, 2 * h, gather_output=False, world_size=tp,
                               sequence_parallel=True, seq_axis=1,
                               tp_comm_overlap=True)
    row = RowParallelLinear(2 * h, h, input_is_parallel=True, world_size=tp,
                           sequence_parallel=True, seq_axis=1,
                           tp_comm_overlap=True)
    cp = col.init(jax.random.PRNGKey(0))
    rp = row.init(jax.random.PRNGKey(1))
    x = jnp.ones((b, s, h), jnp.float32)

    def loss(cp, rp, x):
        def inner(cp, rp, x):
            y, _ = col(cp, x)
            out, _ = row(rp, y)
            return jax.lax.psum(jnp.sum(out * out), "tensor")
        return shard_map(inner, mesh=mesh,
                         in_specs=(P("tensor"), P("tensor"),
                                   P(None, "tensor", None)),
                         out_specs=P())(cp, rp, x)

    c = _census(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1)))(cp, rp, x))
    # fwd: col ring + row ring; bwd: col dX ring + row dX ring
    assert c == {"ppermute": 4 * (tp - 1), "all_gather": 0,
                 "reduce_scatter": 0}, c


# ---------------------------------------------------------------------------
# layer-level: overlap path is bit-identical to the fused SP path at tp=2
# ---------------------------------------------------------------------------

def test_layers_overlap_bit_identical_tp2(mesh_tp2):
    mesh = mesh_tp2
    rng = np.random.RandomState(3)
    tp, b, s, h = 2, 2, 8, 8
    x = jnp.asarray(rng.randn(b, s, h), jnp.float32)

    def build(overlap):
        col = ColumnParallelLinear(h, 2 * h, gather_output=False,
                                   world_size=tp, sequence_parallel=True,
                                   seq_axis=1, tp_comm_overlap=overlap)
        row = RowParallelLinear(2 * h, h, input_is_parallel=True,
                                world_size=tp, sequence_parallel=True,
                                seq_axis=1, tp_comm_overlap=overlap)
        return col, row

    col, row = build(False)
    cp = col.init(jax.random.PRNGKey(0))
    rp = row.init(jax.random.PRNGKey(1))
    rp = {"weight": rp["weight"], "bias": rp["bias"] + 0.25}

    def run(col, row, cp, rp, x):
        def inner(cp, rp, x):
            def loss_of(ps):
                y, _ = col(ps[0], x)
                out, _ = row(ps[1], y)
                return jax.lax.psum(jnp.sum(out * out), "tensor")
            l, g = jax.value_and_grad(loss_of)((cp, rp))
            pm = lambda v: jax.lax.pmean(v, "tensor")
            return pm(l), jax.tree_util.tree_map(pm, g)
        specs = {"weight": P("tensor"), "bias": P("tensor")}
        return shard_map(inner, mesh=mesh,
                         in_specs=(specs, specs, P(None, "tensor", None)),
                         out_specs=(P(), (specs, specs)))(cp, rp, x)

    l_f, g_f = jax.jit(lambda *a: run(*build(False), *a))(cp, rp, x)
    l_o, g_o = jax.jit(lambda *a: run(*build(True), *a))(cp, rp, x)
    assert float(l_o) == float(l_f)
    # weight/input grads are bit-identical; the bias-fold cotangent is the
    # same full-sequence sum computed in a different XLA fusion, which may
    # reassociate the reduction — the documented <=1-ULP fp32 delta
    # (docs/PERF.md "Dependent-collective overlap")
    for a, b_ in zip(jax.tree_util.tree_leaves(g_o),
                     jax.tree_util.tree_leaves(g_f)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-7, atol=0)


# ---------------------------------------------------------------------------
# model-level: GPT SP+overlap == GPT SP == plain TP (the existing contract)
# ---------------------------------------------------------------------------

def test_gpt_sp_overlap_matches_sp_and_tp(mesh_tp2):
    from apex_tpu.models import GPTConfig, GPTModel

    mesh = mesh_tp2
    kw = dict(vocab_size=128, hidden_size=32, num_layers=2,
              num_attention_heads=4, max_position_embeddings=16,
              compute_dtype=jnp.float32, use_flash=False,
              tensor_model_parallel_size=2)
    m_tp = GPTModel(GPTConfig(**kw))
    m_sp = GPTModel(GPTConfig(**kw, sequence_parallel=True))
    m_ov = GPTModel(GPTConfig(**kw, sequence_parallel=True,
                              tp_comm_overlap=True))
    params = m_tp.init(jax.random.PRNGKey(2))
    tokens = jnp.asarray(np.random.RandomState(2).randint(0, 128, (2, 16)))
    specs = m_tp.param_specs(params)

    def run(model, params, tokens):
        def inner(params, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: model.loss(p, tokens, tokens))(params)
            pm = lambda v: jax.lax.pmean(
                jax.lax.pmean(v, "tensor"), "data")
            return pm(loss), jax.tree_util.tree_map(pm, grads)
        return shard_map(inner, mesh=mesh, in_specs=(specs, P()),
                         out_specs=(P(), specs))(params, tokens)

    loss_tp, g_tp = jax.jit(lambda p, t: run(m_tp, p, t))(params, tokens)
    loss_sp, g_sp = jax.jit(lambda p, t: run(m_sp, p, t))(params, tokens)
    loss_ov, g_ov = jax.jit(lambda p, t: run(m_ov, p, t))(params, tokens)

    # overlap vs fused SP at tp=2: the same two partial products summed
    # (a + b is b + a), so the loss and every grad leaf agree to a few
    # ULP of the leaf's largest entry. Not to the bit: the ring's chunked
    # GEMMs have other shapes than the fused one, and a backend's GEMM may
    # block its K-sum by shape (the layer-level test above: <= 1 ULP)
    ulp = float(np.finfo(np.float32).eps)
    np.testing.assert_allclose(float(loss_ov), float(loss_sp), rtol=2 * ulp)
    for a, b in zip(jax.tree_util.tree_leaves(g_ov),
                    jax.tree_util.tree_leaves(g_sp)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=0,
            atol=4 * ulp * float(np.abs(np.asarray(b)).max()))
    # overlap vs plain TP: the existing SP-vs-TP tolerance contract
    np.testing.assert_allclose(float(loss_ov), float(loss_tp), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_ov),
                    jax.tree_util.tree_leaves(g_tp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-6)


def test_gpt_config_overlap_requires_sequence_parallel():
    from apex_tpu.models import GPTConfig, GPTModel

    with pytest.raises(ValueError, match="sequence_parallel"):
        GPTModel(GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                           num_attention_heads=4,
                           tensor_model_parallel_size=2,
                           tp_comm_overlap=True))
    # the layers refuse the combination directly too (no silent
    # fall-through to the fused path for direct layer users)
    with pytest.raises(ValueError, match="sequence_parallel"):
        ColumnParallelLinear(8, 8, world_size=2, tp_comm_overlap=True)
    with pytest.raises(ValueError, match="sequence_parallel"):
        RowParallelLinear(8, 8, world_size=2, tp_comm_overlap=True)


# ---------------------------------------------------------------------------
# trainer wiring: SP(+overlap) through TrainConfig at pp=1, with telemetry
# ---------------------------------------------------------------------------

def _trainer_cfg(sp, ov):
    from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                                 ParallelConfig, TrainConfig)

    return TrainConfig(
        model=ModelConfig(name="gpt", vocab_size=64, hidden_size=32,
                          num_layers=2, num_attention_heads=4,
                          max_position_embeddings=8,
                          sequence_parallel=sp, tp_comm_overlap=ov),
        parallel=ParallelConfig(tensor_model_parallel_size=2),
        batch=BatchConfig(global_batch_size=16, micro_batch_size=2),
        optimizer=OptimizerConfig(name="adam", lr=1e-3),
        opt_level="O0")


def test_hybrid_trainer_sp_overlap_step_and_metrics():
    """SP(+overlap) trainer parity vs the NON-SP trainer —
    loss AND one-step updated params/first moments (losses alone would
    slip wrong gradients), plus the tp/* telemetry."""
    from apex_tpu.training import GPTHybridTrainer

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, (4, 8, 8)))
    targets = jnp.asarray(rng.randint(0, 64, (4, 8, 8)))

    results = {}
    for name, (sp, ov) in {"tp": (False, False), "sp": (True, False),
                           "ov": (True, True)}.items():
        mesh = parallel_state.initialize_model_parallel(
            tensor_model_parallel_size=2)
        try:
            tr = GPTHybridTrainer(_trainer_cfg(sp, ov), mesh)
            state = tr.init_state(jax.random.PRNGKey(0))
            loss, stage, shared, opt_state, _, metrics = jax.jit(
                tr.train_step_with_metrics)(*state, tokens, targets)
            results[name] = (float(loss), (stage, shared),
                             opt_state.exp_avg, metrics.as_floats())
        finally:
            parallel_state.destroy_model_parallel()

    assert results["ov"][0] == results["sp"][0]
    np.testing.assert_allclose(results["sp"][0], results["tp"][0],
                               rtol=1e-5)
    # gradients, not just losses: post-step params and adam first moments
    # of the SP legs must match the non-SP trainer ground truth
    for leg in ("sp", "ov"):
        for a, b in zip(jax.tree_util.tree_leaves(results[leg][1]),
                        jax.tree_util.tree_leaves(results["tp"][1])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(results[leg][2]),
                        jax.tree_util.tree_leaves(results["tp"][2])):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=1e-4)
    m = results["ov"][3]
    assert m["tp/overlap_chunks"] == 2.0
    # M=4 microbatches x 2 layers x (tp-1) x 4 rings x (2*4*32 elems x 4B)
    # per rank, psummed over the 8 mesh devices
    assert m["tp/collective_bytes"] == 4 * 2 * (2 * 1024 + 2 * 1024) * 8
    assert "tp/overlap_chunks" not in results["sp"][3]


def test_model_level_tp_overlap_metrics():
    """tp/* telemetry through the model path (transform), which runs under
    plain full-checking shard_map and is supported on any jax version."""
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.observability import ingraph

    mesh = parallel_state.initialize_model_parallel(
        tensor_model_parallel_size=2)
    try:
        cfg = GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_attention_heads=4, max_position_embeddings=8,
                        compute_dtype=jnp.float32, use_flash=False,
                        tensor_model_parallel_size=2,
                        sequence_parallel=True, tp_comm_overlap=True)
        model = GPTModel(cfg)
        params = model.init(jax.random.PRNGKey(0))
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 64, (2, 8)))
        specs = model.param_specs(params)

        def run(params, tokens):
            def inner(params, tokens):
                out, metrics = ingraph.reap(
                    lambda: model.loss(params, tokens, tokens))()
                pm = lambda v: jax.lax.pmean(
                    jax.lax.pmean(v, "tensor"), "data")
                return pm(out), ingraph.aggregate(
                    metrics, ("data", "tensor"))
            return shard_map(inner, mesh=mesh, in_specs=(specs, P()),
                             out_specs=(P(), P()))(params, tokens)

        loss, metrics = jax.jit(run)(params, tokens)
        got = metrics.as_floats()
        assert got["tp/overlap_chunks"] == 2.0
        # 2 layers x (tp-1) x (2 col + 2 row rings) x (2*4*32 elems x 4B)
        # per rank, psummed over the 8 mesh devices
        assert got["tp/collective_bytes"] == 2 * (2 * 1024 + 2 * 1024) * 8
        assert np.isfinite(float(loss))
    finally:
        parallel_state.destroy_model_parallel()

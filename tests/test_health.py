"""Numerics health watchdog tests: the fused per-leaf stats pass,
trace-time gating (the zero-cost-off contract, asserted on the jaxpr),
first-nonfinite attribution, replica-agreement detection on a multi-device
CPU mesh, crash dumps + the reporter hook, and the HealthConfig threading
through GPTHybridTrainer."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import observability as obs
from apex_tpu.observability import health, ingraph
from jax import shard_map


# ---------------------------------------------------------------------------
# tensor_stats: the fused per-leaf pass
# ---------------------------------------------------------------------------

class TestTensorStats:
    def test_per_leaf_stats(self):
        tree = {
            "a": jnp.asarray([1.0, -3.0, jnp.inf, 2.0], jnp.float32),
            "b": {"c": jnp.asarray([jnp.nan, 0.5], jnp.float32)},
            "ints": jnp.arange(5),  # non-float: ignored
        }
        stats = jax.jit(health.tensor_stats)(tree)
        assert stats.paths == ("['a']", "['b']['c']")
        assert stats.sizes == (4, 2)
        np.testing.assert_allclose(stats.finite_count, [3.0, 1.0])
        assert float(stats.nonfinite_count()) == 2.0
        # abs_max NaN-propagates: leaf a reads inf, leaf b reads NaN
        assert np.isinf(stats.abs_max[0])
        assert np.isnan(stats.abs_max[1])
        # sq_sum is over the FINITE elements (1+9+4, 0.25)
        np.testing.assert_allclose(stats.sq_sum, [14.0, 0.25])
        assert float(stats.first_nonfinite_index()) == 0.0

    def test_clean_tree_and_empty_tree(self):
        stats = health.tensor_stats({"w": jnp.ones((3, 2))})
        assert float(stats.nonfinite_count()) == 0.0
        assert float(stats.first_nonfinite_index()) == -1.0
        assert float(stats.l2()) == pytest.approx(np.sqrt(6.0))
        assert health.tensor_stats({"i": jnp.arange(3)}) is None
        assert health.tensor_stats({}) is None

    def test_underflow_fraction_half_only(self):
        # fp16 subnormal range is (0, 6.1e-5); f32 values there are normal
        tree = {
            "h": jnp.asarray([1e-6, 1.0, 0.0, 2e-5], jnp.float16),
            "f": jnp.asarray([1e-6, 1e-30], jnp.float32),
        }
        stats = health.tensor_stats(tree)
        # 2 of the 4 fp16 elements underflow; zeros don't count; f32
        # leaves contribute nothing to either side of the fraction
        assert float(stats.underflow_fraction()) == pytest.approx(0.5)
        assert stats.half_mask == (False, True)  # dict flattens sorted: f, h
        clean = health.tensor_stats({"f": jnp.ones(4, jnp.float32)})
        assert float(clean.underflow_fraction()) == 0.0

    def test_one_nan_in_a_huge_leaf_is_detected(self):
        """Counting must be int32-exact: an fp32 count is exact only to
        2^24, so one NaN in a 2^25-element leaf (a small embedding table)
        would round away and never be attributed."""
        big = jnp.zeros((2 ** 25,), jnp.bfloat16).at[12345].set(jnp.nan)
        stats = jax.jit(health.tensor_stats)({"emb": big})
        assert int(stats.finite_count[0]) == 2 ** 25 - 1
        assert float(stats.nonfinite_count()) == 1.0
        assert float(stats.first_nonfinite_index()) == 0.0

    def test_treestats_is_a_pytree(self):
        stats = health.tensor_stats({"a": jnp.ones(2)})
        leaves, treedef = jax.tree_util.tree_flatten(stats)
        assert len(leaves) == 4
        back = jax.tree_util.tree_unflatten(treedef, leaves)
        assert back.paths == stats.paths and back.sizes == stats.sizes


# ---------------------------------------------------------------------------
# gating: the zero-cost-off contract (acceptance criterion)
# ---------------------------------------------------------------------------

def _amp_opt_step():
    from apex_tpu.amp.scaler import DynamicLossScale, all_finite
    from apex_tpu.optimizers import FusedSGD

    scaler = DynamicLossScale()
    opt = FusedSGD(lr=0.1)

    def step(params, opt_state, ls, x):
        grads = jax.grad(lambda p: jnp.sum((x @ p) ** 2))(params)
        finite = all_finite(grads)
        new_ls = scaler.update(ls, finite)
        params, opt_state = opt.step(grads, opt_state, params,
                                     grads_finite=finite)
        return params, opt_state, new_ls

    params = jnp.ones((4, 2))
    return step, (params, opt.init(params), scaler.init(), jnp.ones((3, 4)))


class TestZeroCostOff:
    def test_off_path_jaxpr_identical(self):
        """The instrumented amp+optimizer step must trace to the SAME
        jaxpr with (a) no active policy, (b) an explicit level="off"
        policy, (c) an active cheap policy but no collector — the two
        trace-time gates of observe_*, same style as the ingraph no-op
        contract."""
        step, args = _amp_opt_step()
        baseline = str(jax.make_jaxpr(step)(*args))
        with health.activate(health.HealthConfig(level="off")):
            assert str(jax.make_jaxpr(step)(*args)) == baseline
        with health.activate(health.HealthConfig(level="cheap")):
            assert health.active_level() == "cheap"
            assert str(jax.make_jaxpr(step)(*args)) == baseline
        assert health.active() is None

    def test_collector_without_policy_adds_nothing(self):
        step, args = _amp_opt_step()
        # reaping adds the amp/optim metrics but no health stats pass
        assert not any(k.startswith("health/")
                       for k in _reap_names(step, args))

    def test_cheap_level_adds_health_metrics(self):
        step, args = _amp_opt_step()

        def active_step(*a):
            with health.activate(health.HealthConfig(level="cheap")):
                return ingraph.reap(step)(*a)

        _, metrics = jax.jit(active_step)(*args)
        got = metrics.as_floats()
        for key in ("health/grads/nonfinite_count", "health/grads/abs_max",
                    "health/grads/l2", "health/grads/underflow_frac",
                    "health/grads/first_nonfinite_leaf"):
            assert key in got, key
        assert got["health/grads/nonfinite_count"] == 0.0
        assert got["health/grads/first_nonfinite_leaf"] == -1.0
        # cheap level does NOT run the full-tier observers
        assert not any(k.startswith(("health/optim_grads/",
                                     "health/params/")) for k in got)

    def test_full_level_adds_param_stats(self):
        step, args = _amp_opt_step()

        def active_step(*a):
            with health.activate(health.HealthConfig(level="full")):
                return ingraph.reap(step)(*a)

        _, metrics = jax.jit(active_step)(*args)
        got = metrics.as_floats()
        assert "health/optim_grads/nonfinite_count" in got
        assert "health/params/nonfinite_count" in got
        assert got["health/params/abs_max"] > 0.0


def _reap_names(step, args):
    _, metrics = ingraph.reap(step)(*args)
    return set(metrics.values)


# ---------------------------------------------------------------------------
# first-nonfinite attribution (acceptance criterion)
# ---------------------------------------------------------------------------

class TestAttribution:
    def test_injected_inf_names_the_leaf(self):
        from apex_tpu.amp.scaler import DynamicLossScale, all_finite

        scaler = DynamicLossScale(init_scale=4.0)
        big = jnp.float32(3e38)

        def loss_fn(p, poison):
            inject = jnp.where(poison > 0, big * big, jnp.float32(0.0))
            return jnp.sum(p["aa"] ** 2) + jnp.sum(p["zz"]["bad"]) * inject

        def step(p, ls, poison):
            with health.activate(health.HealthConfig(level="cheap")):
                def body(p, ls, poison):
                    grads = jax.grad(loss_fn)(p, poison)
                    finite = all_finite(grads)
                    return scaler.update(ls, finite)
                return ingraph.reap(body)(p, ls, poison)

        p = {"aa": jnp.ones(3), "zz": {"bad": jnp.ones(2)}}
        ls = scaler.init()
        _, metrics = jax.jit(step)(p, ls, jnp.float32(1.0))
        got = metrics.as_floats()
        assert got["amp/overflow_count"] == 1.0
        assert got["health/grads/nonfinite_count"] == 2.0
        att = health.decode_attribution(got)
        assert att == {"grads": "['zz']['bad']"}
        # clean step: no attribution
        _, metrics = jax.jit(step)(p, ls, jnp.float32(0.0))
        assert health.decode_attribution(metrics.as_floats()) == {}

    def test_non_grad_finite_checks_do_not_pollute_grads(self):
        """all_finite is a shared chokepoint: finite-checks of non-grad
        trees (multi_tensor_apply outputs) must not sum into — or
        re-attribute — health/grads/*."""
        from apex_tpu.amp.scaler import all_finite
        from apex_tpu.multi_tensor_apply.multi_tensor_apply import (
            multi_tensor_scale)

        def step(grads, params):
            scaled, _ = multi_tensor_scale(params, 2.0)  # observe=None
            finite = all_finite(grads)
            return jax.tree_util.tree_map(
                lambda s, g: s + 0.0 * g, scaled, grads), finite

        grads = {"g1": jnp.ones(2), "g2": jnp.asarray([jnp.inf])}
        params = {"g1": jnp.ones(2), "g2": jnp.ones(1)}
        with health.activate(health.HealthConfig(level="cheap")):
            _, m = jax.jit(ingraph.reap(step))(grads, params)
        got = m.as_floats()
        # only the GRAD check recorded: one inf total, not params' zero
        # summed in twice, and attribution points into the grads tree
        assert got["health/grads/nonfinite_count"] == 1.0
        assert health.decode_attribution(got) == {"grads": "['g2']"}

        def observed_names(observe):
            def s(t):
                return all_finite(t, observe=observe)
            with health.activate(health.HealthConfig(level="cheap")):
                _, m = ingraph.reap(s)({"x": jnp.ones(1)})
            return set(m.values)

        assert observed_names(None) == set()
        assert {n.split("/")[1] for n in observed_names("master")} \
            == {"master"}

    def test_two_same_name_checks_keep_separate_attribution(self):
        """A step with two all_finite calls (GAN pattern: D grads then G
        grads, both defaulting to "grads") must not overwrite the first
        check's attribution — the second records under grads#2."""
        from apex_tpu.amp.scaler import all_finite

        def step(gD, gG):
            return all_finite(gD), all_finite(gG)

        gD = {"d": jnp.asarray([jnp.inf])}
        gG = {"g": jnp.ones(2)}
        with health.activate(health.HealthConfig(level="cheap")):
            _, m = jax.jit(ingraph.reap(step))(gD, gG)
        got = m.as_floats()
        assert got["health/grads/nonfinite_count"] == 1.0
        assert got["health/grads#2/nonfinite_count"] == 0.0
        att = health.decode_attribution(got)
        assert att == {"grads": "['d']"}  # the inf stays attributed to D

    def test_leaf_paths_side_table(self):
        with health.activate(health.HealthConfig(level="cheap")):
            _, m = ingraph.reap(
                lambda: health.observe_tree(
                    {"x": jnp.ones(1), "y": jnp.ones(1)}, "sidetable")
                or jnp.zeros(()))()
        assert health.leaf_paths("sidetable") == ("['x']", "['y']")
        assert health.leaf_paths("never_observed") is None


# ---------------------------------------------------------------------------
# replica agreement (acceptance criterion: perturbed replica flagged)
# ---------------------------------------------------------------------------

class TestReplicaAgreement:
    def _run(self, stacked):
        mesh = Mesh(np.array(jax.devices()[:4]), ("data",))

        def inner(tree):
            def body(tree):
                local = jax.tree_util.tree_map(lambda l: l[0], tree)
                return health.check_replica_agreement(local, "data",
                                                      name="state")
            _, m = ingraph.reap(body)(tree)
            return ingraph.aggregate(m, "data")

        spec = jax.tree_util.tree_map(lambda _: P("data"), stacked)
        metrics = jax.jit(lambda t: shard_map(
            inner, mesh=mesh, in_specs=(spec,), out_specs=P())(t))(stacked)
        return metrics.as_floats()["health/state/replica_divergence"]

    def test_agreeing_replicas_read_zero(self):
        stacked = {"w": jnp.ones((4, 1, 8)), "b": jnp.zeros((4, 1, 2))}
        assert self._run(stacked) == 0.0

    def test_perturbed_replica_flagged(self):
        stacked = {"w": jnp.ones((4, 1, 8)), "b": jnp.zeros((4, 1, 2))}
        # corrupt one element on replica 1: mean moves by 0.5/4 = 0.125,
        # so the corrupted replica deviates by 0.375, the others by 0.125
        stacked["w"] = stacked["w"].at[1, 0, 3].add(0.5)
        assert self._run(stacked) == pytest.approx(0.375)

    def test_returns_scalar_outside_collector(self):
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

        def inner(x):
            # zero-size and non-float leaves must be skipped, not crash
            tree = {"x": x, "empty": jnp.zeros((0,)), "i": jnp.arange(2)}
            # the returned divergence is PER-RANK (each replica's own
            # deviation from the mean); pmax it to cross a P() out_spec
            d = health.check_replica_agreement(tree, "data")
            return jax.lax.pmax(d, "data")

        out = jax.jit(lambda x: shard_map(
            inner, mesh=mesh, in_specs=P("data"), out_specs=P())(
                x))(jnp.ones(2))
        assert float(out) == 0.0


# ---------------------------------------------------------------------------
# crash dumps + the reporter hook
# ---------------------------------------------------------------------------

def _nonfinite_payload():
    """A payload as the attribution flow produces it (side table warmed)."""
    with health.activate(health.HealthConfig(level="cheap")):
        _, m = ingraph.reap(
            lambda: health.observe_tree(
                {"ok": jnp.ones(2),
                 "boom": jnp.asarray([jnp.inf])}, "grads")
            or jnp.zeros(()))()
    return m.as_floats()


class TestCrashDump:
    def test_dump_contents_and_roundtrip(self, tmp_path):
        payload = _nonfinite_payload()
        assert health.payload_nonfinite(payload)
        cfg = health.HealthConfig(level="cheap", on_nonfinite="dump",
                                  dump_dir=tmp_path)
        dump = health.CrashDump.from_payload(7, payload, cfg)
        assert dump.attribution == {"grads": "['boom']"}
        path = dump.write(tmp_path / "sub")
        text = open(path).read()
        # STRICT json: a bare Infinity literal (abs_max of an overflow
        # dump) would make the file unparsable by jq/JS/Go tooling
        doc = json.loads(text, parse_constant=lambda c: pytest.fail(
            f"non-standard JSON literal {c} in crash dump"))
        assert doc["step"] == 7
        assert doc["metrics"]["health/grads/nonfinite_count"] == 1.0
        assert doc["metrics"]["health/grads/abs_max"] == "Infinity"
        assert doc["attribution"] == {"grads": "['boom']"}
        assert doc["config"]["level"] == "cheap"
        assert doc["versions"]["jax"] == jax.__version__
        assert doc["wall_time"] > 0

    def test_monitor_dump_and_raise_and_skip(self, tmp_path):
        payload = _nonfinite_payload()
        clean = {"health/grads/nonfinite_count": 0.0,
                 "amp/overflow_count": 0.0}
        assert not health.payload_nonfinite(clean)

        dumper = health.HealthConfig(
            level="cheap", on_nonfinite="dump",
            dump_dir=tmp_path).reporter_hook()
        dumper(3, clean)
        assert dumper.dumps == []
        dumper(4, payload)
        assert len(dumper.dumps) == 1 and "step00000004" in dumper.dumps[0]

        raiser = health.HealthConfig(
            level="cheap", on_nonfinite="raise",
            dump_dir=tmp_path).reporter_hook()
        with pytest.raises(health.NonFiniteError) as exc:
            raiser(5, payload)
        assert exc.value.dump.step == 5
        assert exc.value.dump_path and "step00000005" in exc.value.dump_path
        assert "['boom']" in str(exc.value)

        skipper = health.HealthConfig(
            level="cheap", on_nonfinite="skip").reporter_hook()
        skipper(6, payload)  # no dump, no raise
        assert skipper.dumps == []

    def test_amp_overflow_alone_triggers(self):
        assert health.payload_nonfinite({"amp/overflow_count": 1.0})

    def test_reporter_runs_hooks_after_sinks(self, tmp_path):
        order = []

        class Spy(obs.JSONLSink):
            def __init__(self):
                pass

            def emit(self, step, metrics, spans=()):
                order.append("sink")

            def close(self):
                pass

        rep = obs.StepReporter([Spy()], registry=obs.MetricsRegistry(),
                               hooks=[lambda s, p: order.append("hook")])
        rep.report(0)
        assert order == ["sink", "hook"]

    def test_hooks_see_off_interval_steps(self):
        """interval=N samples the SINKS, not the watchdog: a transient
        non-finite step between reports must still reach the hooks."""
        seen, emitted = [], []

        class Spy(obs.JSONLSink):
            def __init__(self):
                pass

            def emit(self, step, metrics, spans=()):
                emitted.append(step)

            def close(self):
                pass

        rep = obs.StepReporter([Spy()], registry=obs.MetricsRegistry(),
                               interval=3,
                               hooks=[lambda s, p: seen.append((s, p))])
        for i in range(5):
            rep.report(i, metrics={"health/grads/nonfinite_count":
                                   1.0 if i == 1 else 0.0})
        assert emitted == [0, 3]
        assert [s for s, _ in seen] == [0, 1, 2, 3, 4]
        assert seen[1][1]["health/grads/nonfinite_count"] == 1.0
        # off-interval steps WITHOUT metrics stay fetch-free and unseen
        seen.clear()
        rep.report(7)
        assert seen == []

    def test_consecutive_tolerates_calibration_overflows(self, tmp_path):
        """consecutive=2 ignores isolated overflow reports (dynamic
        loss-scale calibration overflows by design every growth interval)
        and fires only when the streak shows real divergence."""
        payload = _nonfinite_payload()
        clean = {"amp/overflow_count": 0.0}
        hook = health.HealthConfig(
            level="cheap", on_nonfinite="raise", dump_dir=tmp_path,
            consecutive=2).reporter_hook()
        hook(0, payload)            # routine calibration overflow
        assert hook.streak == 1 and hook.dumps == []
        hook(1, clean)              # backoff cleared it -> streak resets
        assert hook.streak == 0
        hook(2, payload)
        with pytest.raises(health.NonFiniteError):
            hook(3, payload)        # second consecutive: real divergence
        assert len(hook.dumps) == 1

    def test_config_validation(self):
        with pytest.raises(ValueError):
            health.HealthConfig(level="loud")
        with pytest.raises(ValueError):
            health.HealthConfig(on_nonfinite="explode")
        with pytest.raises(ValueError):
            health.HealthConfig(consecutive=0)


# ---------------------------------------------------------------------------
# HealthConfig through GPTHybridTrainer (acceptance criterion)
# ---------------------------------------------------------------------------

def _small_cfg():
    from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                                 ParallelConfig, TrainConfig)
    tp, pp, dp = 2, 2, 2
    M, mb, seq = 2, 2, 8
    cfg = TrainConfig(
        model=ModelConfig(name="gpt", vocab_size=64, hidden_size=32,
                          num_layers=2 * pp, num_attention_heads=4,
                          max_position_embeddings=seq),
        parallel=ParallelConfig(tensor_model_parallel_size=tp,
                                pipeline_model_parallel_size=pp),
        batch=BatchConfig(global_batch_size=M * mb * dp,
                          micro_batch_size=mb),
        optimizer=OptimizerConfig(name="adam", lr=1e-2, weight_decay=0.0),
        opt_level="O0")
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 64, (M, dp * mb, seq)))
    targets = jnp.asarray(rng.randint(0, 64, (M, dp * mb, seq)))
    return cfg, tokens, targets


from _jaxpr_utils import jaxpr_str as _jaxpr_str  # noqa: E402


def test_trainer_health_off_is_jaxpr_identical_and_cheap_attributes():
    """level="off" leaves both trainer step programs identical to an
    unconfigured trainer's; level="cheap" surfaces the health metrics in
    the same Metrics pytree."""
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    cfg, tokens, targets = _small_cfg()
    mesh = cfg.initialize_mesh(devices=jax.devices())
    try:
        base = GPTHybridTrainer(cfg, mesh)
        assert base.health.level == "off"  # from cfg.build_health()
        off = GPTHybridTrainer(cfg, mesh,
                               health=health.HealthConfig(level="off"))
        cheap = GPTHybridTrainer(
            cfg, mesh, health=health.HealthConfig(level="cheap"))
        state = base.init_state(jax.random.PRNGKey(0))
        args = state + (tokens, targets)

        base_plain = _jaxpr_str(base.train_step, *args)
        assert _jaxpr_str(off.train_step, *args) == base_plain
        # an active policy without a collector is also free: the plain
        # (uninstrumented) step of the CHEAP trainer matches too
        assert _jaxpr_str(cheap.train_step, *args) == base_plain
        base_metrics = _jaxpr_str(base.train_step_with_metrics, *args)
        assert _jaxpr_str(off.train_step_with_metrics, *args) \
            == base_metrics
        assert "health" not in base_metrics

        *_, metrics = jax.jit(cheap.train_step_with_metrics)(*args)
        got = metrics.as_floats()
        for key in ("health/grads/nonfinite_count",
                    "health/grads/first_nonfinite_leaf",
                    "amp/overflow_count"):
            assert key in got, key
        assert got["health/grads/nonfinite_count"] == 0.0
        assert got["health/grads/first_nonfinite_leaf"] == -1.0
    finally:
        parallel_state.destroy_model_parallel()


def test_trainer_full_level_replica_checks():
    """level="full" adds the data-axis replica-agreement checks on params
    and post-allreduce grads — both must read 0.0 on a healthy step."""
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    cfg, tokens, targets = _small_cfg()
    mesh = cfg.initialize_mesh(devices=jax.devices())
    try:
        trainer = GPTHybridTrainer(
            cfg, mesh, health=health.HealthConfig(level="full"))
        state = trainer.init_state(jax.random.PRNGKey(0))
        *_, metrics = jax.jit(trainer.train_step_with_metrics)(
            *state, tokens, targets)
        got = metrics.as_floats()
        # ~0, not exactly 0: the pmean reduction order can leave an ulp
        # of residue on replicated state (see check_replica_agreement)
        assert got["health/params/replica_divergence"] <= 1e-6
        assert got["health/ddp_grads/replica_divergence"] <= 1e-6
        assert "health/optim_grads/nonfinite_count" in got
    finally:
        parallel_state.destroy_model_parallel()


def test_trainconfig_builds_health():
    from apex_tpu.config import TrainConfig

    cfg = TrainConfig(health_level="cheap", health_on_nonfinite="dump",
                      health_consecutive=3, health_dump_dir="dumps")
    h = cfg.build_health()
    assert h.level == "cheap" and h.on_nonfinite == "dump"
    assert h.consecutive == 3 and h.dump_dir == "dumps"
    # serialization round-trips the new fields
    assert TrainConfig.from_dict(cfg.to_dict()) == cfg
    assert TrainConfig().build_health().level == "off"

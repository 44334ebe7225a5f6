"""Selective activation rematerialization (apex_tpu/remat.py).

Pins the four contracts of the policy subsystem:

1. **Back-compat**: the deprecated ``remat: bool`` maps to
   ``remat_policy="full"|"none"`` (DeprecationWarning on True), configs
   round-trip through the JSON sidecar form, and ``policy="full"`` traces
   a program *identical* to the legacy ``remat=True`` one — with zero
   ``name`` equations, so it cannot have drifted from the pre-policy
   program (which had no tag machinery at all).
2. **Structure**: under ``selective`` the jaxpr census shows exactly the
   registry-named residuals tagged in the forward, none of the saved
   names recomputed, and the flash-attention *forward* kernel absent
   from the remat region (its backward kernels stay, by construction).
3. **Determinism**: the recomputed forward regenerates bit-identical
   dropout keep masks under every policy — both the in-kernel
   (counter-based, seed-keyed) flash dropout and the key-threaded hidden
   dropout — asserted as grad equality against the unrematerialized
   program.
4. **Memory**: ``memory_budget`` temp bytes order
   ``none > selective > full`` on a GPT train step — the trade the
   policies exist to navigate.
"""

import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _jaxpr_utils import iter_eqns, jaxpr_str
from apex_tpu import remat
from apex_tpu.remat import RematPolicy


# ---------------------------------------------------------------------------
# policy object: validation + resolution
# ---------------------------------------------------------------------------

class TestRematPolicy:
    def test_modes_and_validation(self):
        assert RematPolicy().mode == "none"
        for mode in ("none", "full", "selective", "offload"):
            RematPolicy(mode=mode)
        with pytest.raises(ValueError):
            RematPolicy(mode="everything")
        with pytest.raises(ValueError):            # unregistered name
            RematPolicy(mode="selective", names=("rogue",))
        with pytest.raises(ValueError):            # names need a name mode
            RematPolicy(mode="full", names=("qkv_out",))
        p = RematPolicy(mode="selective", names=["qkv_out", "ln_out"])
        assert p.names == ("qkv_out", "ln_out")    # normalized to tuple
        assert p.save_names == ("qkv_out", "ln_out")
        assert RematPolicy(mode="selective").save_names \
            == remat.SELECTIVE_SAVE

    def test_resolve_spellings(self):
        assert RematPolicy.resolve(None).mode == "none"
        assert RematPolicy.resolve(False).mode == "none"
        assert RematPolicy.resolve(True).mode == "full"   # schedules flag
        assert RematPolicy.resolve("selective").mode == "selective"
        p = RematPolicy(mode="offload")
        assert RematPolicy.resolve(p) is p
        with pytest.raises(TypeError):
            RematPolicy.resolve(3.14)

    def test_legacy_bool_warns(self):
        with pytest.warns(DeprecationWarning, match="remat_policy"):
            p = RematPolicy.resolve(None, legacy_bool=True, owner="X")
        assert p.mode == "full"
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # False must stay silent
            assert RematPolicy.resolve(
                None, legacy_bool=False).mode == "none"

    def test_uses_names_gate(self):
        assert not RematPolicy(mode="none").uses_names
        assert not RematPolicy(mode="full").uses_names
        assert RematPolicy(mode="selective").uses_names
        assert RematPolicy(mode="offload").uses_names


# ---------------------------------------------------------------------------
# config threading + round-trip (satellite: back-compat)
# ---------------------------------------------------------------------------

class TestConfigRoundTrip:
    def test_legacy_bool_round_trips_and_warns(self):
        from apex_tpu.config import ModelConfig, TrainConfig

        cfg = TrainConfig(model=ModelConfig(
            name="gpt", vocab_size=64, hidden_size=32, num_layers=2,
            num_attention_heads=4, max_position_embeddings=16, remat=True))
        cfg2 = TrainConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
        assert cfg2.model.remat is True and cfg2.model.remat_policy is None
        with pytest.warns(DeprecationWarning):
            model = cfg2.build_model()
        assert model.remat_policy.mode == "full"

    def test_policy_and_names_round_trip(self):
        from apex_tpu.config import ModelConfig, TrainConfig

        cfg = TrainConfig(model=ModelConfig(
            name="gpt", vocab_size=64, hidden_size=32, num_layers=2,
            num_attention_heads=4, max_position_embeddings=16,
            remat_policy="selective", remat_names=("qkv_out", "flash_ctx")))
        cfg2 = TrainConfig.from_dict(json.loads(json.dumps(cfg.to_dict())))
        assert cfg2.model.remat_policy == "selective"
        assert cfg2.model.remat_names == ("qkv_out", "flash_ctx")
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            model = cfg2.build_model()
        assert model.remat_policy.mode == "selective"
        assert model.remat_policy.save_names == ("qkv_out", "flash_ctx")

    def test_default_stays_silent_and_none(self):
        from apex_tpu.config import ModelConfig, TrainConfig

        cfg = TrainConfig(model=ModelConfig(
            name="gpt", vocab_size=64, hidden_size=32, num_layers=2,
            num_attention_heads=4, max_position_embeddings=16))
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            model = cfg.build_model()
        assert model.remat_policy.mode == "none"

    def test_names_without_name_policy_rejected(self):
        from apex_tpu.models import GPTConfig, GPTModel

        with pytest.raises(ValueError, match="remat_names"):
            GPTModel(GPTConfig(
                vocab_size=64, hidden_size=32, num_layers=2,
                num_attention_heads=4, remat_policy="full",
                remat_names=("qkv_out",)))


# ---------------------------------------------------------------------------
# jaxpr structure: identity + selective census (acceptance criteria)
# ---------------------------------------------------------------------------

# pallas-eligible shapes: seq % 128 == 0, head_dim % 8 == 0 — the flash
# kernel (interpret mode on CPU) must be in the program for the census
_GPT_KW = dict(vocab_size=256, hidden_size=64, num_layers=2,
               num_attention_heads=4, max_position_embeddings=128,
               compute_dtype=jnp.float32, params_dtype=jnp.float32,
               use_flash=True)


def _gpt_grad_jaxpr(policy=None, legacy=False, dropout=False, **kw):
    from apex_tpu.models import GPTConfig, GPTModel

    model = GPTModel(GPTConfig(**{**_GPT_KW, **kw}, remat=legacy,
                               remat_policy=policy))
    params = model.init(jax.random.PRNGKey(0))
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (1, 128)))
    rng = jax.random.PRNGKey(1) if dropout else None
    fn = jax.grad(lambda p: model.loss(p, tokens, tokens, dropout_rng=rng))
    return jax.make_jaxpr(fn)(params), model, params, tokens


def _names_in(jaxpr) -> set:
    return {e.params["name"] for e in iter_eqns(jaxpr.jaxpr)
            if e.primitive.name == "name"}


def _remat_bodies(jaxpr):
    return [e.params["jaxpr"] for e in iter_eqns(jaxpr.jaxpr)
            if e.primitive.name in ("remat2", "checkpoint")]


def _count_in(jaxpr_like, prim: str) -> int:
    return sum(1 for e in iter_eqns(getattr(jaxpr_like, "jaxpr",
                                            jaxpr_like))
               if e.primitive.name == prim)


class TestJaxprStructure:
    def test_full_identical_to_legacy_and_tagfree(self):
        """policy="full" IS the pre-policy remat=True program: same jaxpr
        as the legacy bool spelling, and zero name equations (the tag
        machinery provably absent)."""
        j_full, model, params, tokens = _gpt_grad_jaxpr("full")
        with pytest.warns(DeprecationWarning):
            j_legacy, lmodel, lparams, _ = _gpt_grad_jaxpr(None,
                                                           legacy=True)
        f = jax.grad(lambda p: model.loss(p, tokens, tokens))
        lf = jax.grad(lambda p: lmodel.loss(p, tokens, tokens))
        assert jaxpr_str(f, params) == jaxpr_str(lf, lparams)
        assert not _names_in(j_full)
        assert _remat_bodies(j_full)

    def test_none_identical_to_default_and_rematfree(self):
        j_none, model, params, tokens = _gpt_grad_jaxpr("none")
        j_default, dmodel, dparams, _ = _gpt_grad_jaxpr(None)
        f = jax.grad(lambda p: model.loss(p, tokens, tokens))
        df = jax.grad(lambda p: dmodel.loss(p, tokens, tokens))
        assert jaxpr_str(f, params) == jaxpr_str(df, dparams)
        assert not _names_in(j_none)
        assert not _remat_bodies(j_none)

    def test_selective_census(self):
        """The acceptance census: every registry tag emitted in the
        forward; saved names NOT recomputed inside the remat region; the
        flash *forward* kernel absent from the recompute (only the one
        backward kernel remains), while full remat reruns it there."""
        j_sel, *_ = _gpt_grad_jaxpr("selective")
        # every registry name is emitted (the flash pair comes from the
        # kernel's custom_vjp fwd rule)
        assert _names_in(j_sel) == set(remat.CHECKPOINT_NAMES)
        bodies = _remat_bodies(j_sel)
        assert bodies
        body_names = set().union(*[{e.params["name"] for e in iter_eqns(b)
                                    if e.primitive.name == "name"}
                                   for b in bodies])
        # saved residuals are dropped from the recompute by DCE; only the
        # deliberately-recomputed LN tier may reappear
        assert body_names <= {"ln_out"}, body_names
        sel_kernels = sum(_count_in(b, "pallas_call") for b in bodies)

        j_full, *_ = _gpt_grad_jaxpr("full")
        full_kernels = sum(_count_in(b, "pallas_call")
                           for b in _remat_bodies(j_full))
        # full: fwd recompute + the backward kernel (dq, dk and dv in one
        # pass since PR 33); selective: the backward kernel only
        assert full_kernels == 2 and sel_kernels == 1, \
            (full_kernels, sel_kernels)
        # both programs run the real forward kernel exactly once outside
        assert _count_in(j_sel, "pallas_call") - sel_kernels == 1
        assert _count_in(j_full, "pallas_call") - full_kernels == 1

    def test_offload_inserts_host_transfers(self):
        j_off, *_ = _gpt_grad_jaxpr("offload")
        assert _names_in(j_off) == set(remat.CHECKPOINT_NAMES)
        # each offloaded residual crosses to host and back
        n_dput = _count_in(j_off, "device_put")
        assert n_dput >= 2 * (len(remat.SELECTIVE_SAVE) - 1), n_dput

    def test_custom_names_narrow_the_saved_set(self):
        j, *_ = _gpt_grad_jaxpr("selective",
                                remat_names=("mlp_fc1_out", "mlp_fc2_out"))
        bodies = _remat_bodies(j)
        body_names = set().union(*[{e.params["name"] for e in iter_eqns(b)
                                    if e.primitive.name == "name"}
                                   for b in bodies])
        # everything outside the custom save-list is now fair recompute
        assert "qkv_out" in body_names and "ln_out" in body_names
        assert "mlp_fc1_out" not in body_names
        # flash residuals unsaved -> the fwd kernel is BACK in the remat
        # region (the failure mode the default save-list exists to avoid)
        assert sum(_count_in(b, "pallas_call") for b in bodies) == 2

    def test_bert_selective_traces_with_tags(self):
        from apex_tpu.models import BertConfig, BertModel

        model = BertModel(BertConfig(
            vocab_size=256, hidden_size=64, num_layers=2,
            num_attention_heads=4, max_position_embeddings=128,
            compute_dtype=jnp.float32, params_dtype=jnp.float32,
            use_flash=True, remat_policy="selective"))
        params = model.init(jax.random.PRNGKey(0))
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (1, 128)))
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: model.loss(p, tokens, tokens)))(params)
        assert {"qkv_out", "attn_proj_out", "flash_ctx", "flash_lse",
                "mlp_fc1_out", "mlp_fc2_out", "ln_out"} <= _names_in(jaxpr)
        assert _remat_bodies(jaxpr)


# ---------------------------------------------------------------------------
# schedules accept policies (bool | str | RematPolicy)
# ---------------------------------------------------------------------------

class TestSchedulesRemat:
    def _setup(self):
        from apex_tpu.transformer.pipeline_parallel import (
            forward_backward_no_pipelining)

        w = jnp.asarray(np.random.RandomState(0).randn(8, 8), jnp.float32)

        def stage(params, x, idx):
            h = remat.tag(jnp.tanh(x @ params), "qkv_out")
            return h @ params

        def loss_fn(y, m):
            return jnp.mean(y ** 2)

        batch = jnp.asarray(
            np.random.RandomState(1).randn(2, 4, 8), jnp.float32)
        run = lambda r: forward_backward_no_pipelining(
            stage, batch, w, loss_fn=loss_fn, remat=r)
        return run

    def test_policy_spellings_agree_numerically(self):
        run = self._setup()
        base_loss, base_grads = run(False)
        for r in (True, "full", "selective",
                  RematPolicy(mode="selective", names=("qkv_out",))):
            loss, grads = run(r)
            np.testing.assert_allclose(loss, base_loss, rtol=1e-6)
            np.testing.assert_allclose(grads, base_grads, rtol=1e-6)

    def test_bool_true_is_full(self):
        import functools
        run = self._setup()
        assert jaxpr_str(functools.partial(run, True)) \
            == jaxpr_str(functools.partial(run, "full"))
        assert jaxpr_str(functools.partial(run, False)) \
            == jaxpr_str(functools.partial(run, "none"))


# ---------------------------------------------------------------------------
# dropout determinism under recompute (satellite)
# ---------------------------------------------------------------------------

_POLICIES = ("full", "selective", "offload")


class TestDropoutUnderRemat:
    def test_flash_inkernel_dropout_bit_identical(self):
        """The in-kernel (counter-based, seed-keyed) flash dropout must
        regenerate the SAME keep mask when the forward is recomputed:
        grads through a checkpointed call equal the unrematerialized
        grads bitwise. A single flipped mask bit would shift entries by
        O(grad), not epsilon."""
        from apex_tpu.ops.flash_attention import flash_attention

        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(1, 2, 128, 16), jnp.float32)
        k = jnp.asarray(rng.randn(1, 2, 128, 16), jnp.float32)
        v = jnp.asarray(rng.randn(1, 2, 128, 16), jnp.float32)
        dy = jnp.asarray(rng.randn(1, 2, 128, 16), jnp.float32)

        def f(q, k, v):
            out = flash_attention(q, k, v, causal=True, use_pallas=True,
                                  dropout_rate=0.3, dropout_seed=7,
                                  checkpoint_names=True)
            return jnp.sum(out * dy)

        base = jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v)
        for mode in _POLICIES:
            wrapped = RematPolicy(mode=mode).wrap(f)
            got = jax.jit(jax.grad(wrapped, argnums=(0, 1, 2)))(q, k, v)
            for b, g in zip(base, got):
                np.testing.assert_array_equal(np.asarray(b), np.asarray(g),
                                              err_msg=mode)

    def test_gpt_dropout_masks_stable_across_policies(self):
        """Model level: hidden + embedding dropout (key-threaded) and
        flash attention dropout (in-kernel) together. Grads under every
        policy match the unrematerialized program far below the O(1)
        signature of a regenerated-differently mask."""
        from apex_tpu.models import GPTConfig, GPTModel

        kw = {**_GPT_KW, "hidden_dropout": 0.1, "attention_dropout": 0.1}
        tokens = jnp.asarray(
            np.random.RandomState(0).randint(0, 256, (1, 128)))
        rng = jax.random.PRNGKey(3)

        def grads_for(policy):
            model = GPTModel(GPTConfig(**kw, remat_policy=policy))
            params = model.init(jax.random.PRNGKey(0))
            return params, jax.jit(jax.grad(
                lambda p: model.loss(p, tokens, tokens,
                                     dropout_rng=rng)))(params)

        p_base, base = grads_for("none")
        for mode in _POLICIES:
            p_got, got = grads_for(mode)
            jax.tree_util.tree_map(
                lambda a, b: np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-7,
                    err_msg=mode), base, got)


# ---------------------------------------------------------------------------
# memory accounting: the frontier the policies navigate
# ---------------------------------------------------------------------------

def test_temp_bytes_ordering_none_selective_full():
    """The acceptance ordering on a GPT train-shaped program:
    save-everything > save-GEMM/flash-outputs > save-carry-only. Measured
    off the compiled executables' memory_analysis — the same numbers
    bench_gpt_remat and StepReporter.attach_memory_budget report."""
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.observability.costs import memory_budget

    kw = dict(vocab_size=512, hidden_size=128, num_layers=2,
              num_attention_heads=4, max_position_embeddings=256,
              compute_dtype=jnp.float32, params_dtype=jnp.float32,
              use_flash=True)
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, 512, (4, 256)))

    def temp_bytes(policy):
        model = GPTModel(GPTConfig(**kw, remat_policy=policy))
        params = model.init(jax.random.PRNGKey(0))
        compiled = jax.jit(jax.grad(
            lambda p: model.loss(p, tokens, tokens))).lower(
            params).compile()
        budget = memory_budget(compiled)
        if budget is None:
            pytest.skip("backend exposes no memory analysis")
        return budget["temp_bytes"]

    none_b, sel_b, full_b = (temp_bytes(p)
                             for p in ("none", "selective", "full"))
    assert none_b > sel_b > full_b, (none_b, sel_b, full_b)


# ---------------------------------------------------------------------------
# hybrid trainer: policy threads through the whole tp x pp x dp step
# ---------------------------------------------------------------------------

def _trainer_cfg(**model_overrides):
    from apex_tpu.config import (BatchConfig, ModelConfig, OptimizerConfig,
                                 ParallelConfig, TrainConfig)
    tp, pp, dp = 2, 2, 2
    M, mb, seq = 2, 2, 8
    cfg = TrainConfig(
        model=ModelConfig(name="gpt", vocab_size=64, hidden_size=32,
                          num_layers=2 * pp, num_attention_heads=4,
                          max_position_embeddings=seq, **model_overrides),
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


def test_trainer_full_policy_jaxpr_identical_to_legacy_bool():
    """The PR 3/4-style identity assertion at the trainer level:
    remat_policy="full" traces the same hybrid train step as the
    deprecated remat=True, with zero name equations; and a selective
    trainer's step carries the registry tags + a remat region."""
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    cfg_full, tokens, targets = _trainer_cfg(remat_policy="full")
    cfg_legacy, _, _ = _trainer_cfg(remat=True)
    cfg_sel, _, _ = _trainer_cfg(remat_policy="selective")
    mesh = cfg_full.initialize_mesh(devices=jax.devices())
    try:
        full = GPTHybridTrainer(cfg_full, mesh)
        with pytest.warns(DeprecationWarning):
            legacy = GPTHybridTrainer(cfg_legacy, mesh)
        assert full.remat_policy.mode == "full"
        assert legacy.remat_policy.mode == "full"
        state = full.init_state(jax.random.PRNGKey(0))
        args = state + (tokens, targets)
        j_full = jaxpr_str(full.train_step, *args)
        assert jaxpr_str(legacy.train_step, *args) == j_full

        # by structure, not by the printed text (which wraps a name
        # equation over lines as it pleases): the equations' primitives
        def eqns(trainer):
            return list(iter_eqns(
                jax.make_jaxpr(trainer.train_step)(*args).jaxpr))

        def tags(trainer):
            return {e.params["name"] for e in eqns(trainer)
                    if e.primitive.name == "name"}

        def rematerializes(trainer):
            return any(e.primitive.name in ("remat2", "checkpoint")
                       for e in eqns(trainer))

        assert tags(full) == set() and rematerializes(full)

        sel = GPTHybridTrainer(cfg_sel, mesh)
        assert sel.remat_policy.uses_names
        assert rematerializes(sel)
        # seq=8 takes the XLA attention fallback, which still tags the
        # context; the GEMM/LN tags come from the layer body
        assert tags(sel) >= {"qkv_out", "attn_proj_out", "mlp_fc1_out",
                             "mlp_fc2_out", "ln_out", "flash_ctx"}
    finally:
        parallel_state.destroy_model_parallel()


def test_trainer_selective_step_runs_and_matches_none():
    """One real optimizer step under selective remat on the 8-device
    mesh reproduces the unrematerialized step's loss and updated params
    (recompute changes schedule, not math)."""
    from apex_tpu.training import GPTHybridTrainer
    from apex_tpu.transformer import parallel_state

    cfg_none, tokens, targets = _trainer_cfg()
    cfg_sel, _, _ = _trainer_cfg(remat_policy="selective")
    mesh = cfg_none.initialize_mesh(devices=jax.devices())
    try:
        t_none = GPTHybridTrainer(cfg_none, mesh)
        t_sel = GPTHybridTrainer(cfg_sel, mesh)
        s0 = t_none.init_state(jax.random.PRNGKey(0))
        s1 = t_sel.init_state(jax.random.PRNGKey(0))
        loss0, *out0 = jax.jit(t_none.train_step)(*s0, tokens, targets)
        loss1, *out1 = jax.jit(t_sel.train_step)(*s1, tokens, targets)
        np.testing.assert_allclose(float(loss0), float(loss1), rtol=1e-6)
        # the GRADIENT is what recompute must not change: Adam's first
        # moment after one step is (1 - beta1) g, held leaf by leaf to
        # float32 roundoff of the leaf's largest entry
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=0,
                atol=2e-6 * float(np.abs(np.asarray(a)).max())),
            out0[2].exp_avg, out1[2].exp_avg)
        # the updated params agree to a thousandth of the largest step
        # Adam takes (lr 1e-2): where an entry's gradient is itself
        # rounding noise (|g| ~ eps = 1e-8) the normalised step
        # m / (sqrt(v) + eps) turns the last bits of g into any step up
        # to lr, so those entries (biases that start at zero) are not
        # bit-comparable between two schedules of the same sums
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
            out0[0], out1[0])
    finally:
        parallel_state.destroy_model_parallel()

"""The pattern decoder as a Nemotron-H hybrid (Mamba-2 layers with a
per-slot state beside the paged KV pool, a latent squared-ReLU expert
layer, attention without positions; one sub-block a layer) against the
plain reference of ``benchmark/families/nemotron_h_reference.py``, at a size
the CPU holds: hidden 64, pattern ``MEM*EME``, 4 Mamba heads of 8 in 2
groups with a state of 16 and chunks of 8, 4 query heads on 1 KV head of 16,
16 experts top-4 of which 4 are held, latent 32, block 8. The weights are
the reference's (bfloat16-stored); the program computes in float32 here so
that agreement is tight: what is left is the order of float32 sums (the
chunked scan against the token-by-token recurrence, the sorted expert
product against experts x tokens), 1e-4 of logits of order 1."""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.pattern_decoder import PatternDecoder
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.ops import mamba2
from apex_tpu.serving import PagedServingEngine, Request, SlotScheduler
from apex_tpu.serving.cache import (KindBlockAllocator, KindPagedKVCache,
                                    SlotStateCache, StateSpec)
from apex_tpu.transformer.expert_parallel import HeldExpertsMLP
from benchmark.families import nemotron_h as family

reference = family.reference

CFG = {
    "vocab_size": 128, "hidden_size": 64, "hybrid_override_pattern": "MEM*EME",
    "num_hidden_layers": 7, "num_attention_heads": 4,
    "num_key_value_heads": 1, "head_dim": 16, "mamba_num_heads": 4,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 8, "n_routed_experts": 4,
    "first_held_expert": 4, "router_width": 16, "num_experts_per_tok": 4,
    "routed_scaling_factor": 5, "moe_latent_size": 32,
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 96,
    "layer_norm_epsilon": 1e-5, "max_position_embeddings": 256,
    "initializer_range": 0.1, "router_bias_std": 0.3,
    "routed_gain": 1.15,
}
BLOCK = 8
TOL = dict(atol=3e-4, rtol=2e-4)


def program(cfg=CFG, **over):
    model = family.model(cfg)
    return PatternDecoder(dataclasses.replace(
        model.cfg, compute_dtype=jnp.float32, **over))


def weights(cfg=CFG, seed=5):
    lo, hi = reference.seed_key(seed)
    return jax.jit(lambda lo, hi: reference.make_weights(cfg, lo, hi))(lo, hi)


def test_the_three_kinds_are_triples_beside_the_two_that_were():
    cfg = program().cfg
    assert cfg.layer_types == ("mamba", "moe", "mamba", "attention", "moe",
                               "mamba", "moe")
    kinds = cfg.cache_kinds
    # only kinds that hold something, and what: blocks or a state
    assert list(kinds) == ["mamba", "attention"]
    assert kinds["attention"] == (1, None)
    assert kinds["mamba"] == StateSpec(3, 4 * 8 + 2 * 2 * 16, 4, 4, 8, 16)
    assert program().stats_shape == (3, 5)         # a row an E layer
    with pytest.raises(ValueError, match="one sub-block"):
        dataclasses.replace(cfg, layer_types=("full_attention",))


@pytest.fixture(scope="module")
def served():
    """Seven prompts, shorter than their bucket and no multiple of the
    chunk, through the scheduler over the paged engine on THREE slots: they
    are admitted at different steps and every slot is given anew after a
    release."""
    model, w = program(), weights()
    engine = PagedServingEngine(
        model, w, max_seqs=3, max_len=96, prefill_len=[16, 32],
        block_size=BLOCK, cache_dtype=jnp.float32,
        num_blocks={"attention": 3 * 12 + 1})
    registry = MetricsRegistry()
    sched = SlotScheduler(engine, registry=registry)
    rng = np.random.default_rng(0)
    lengths = (5, 20, 13, 32, 3, 9, 27)
    prompts = [rng.integers(1, 128, n).tolist() for n in lengths]
    for i, p in enumerate(prompts):
        sched.submit(Request(prompt=p, max_new_tokens=10 + 3 * i,
                             temperature=0.0, request_id=i))
    gauges = []
    while sched.pending:
        sched.step()
        snap = registry.snapshot()
        gauges.append((snap.get("serve/state_slots_in_use"),
                       snap.get("serve/state_bytes_held"),
                       snap.get("serve/blocks_in_use/attention")))
    done = {c.request_id: c for c in sched.drain_completed()}
    return dict(model=model, w=w, engine=engine, prompts=prompts,
                streams=[list(done[i].tokens) for i in range(len(prompts))],
                counters=registry.snapshot(), gauges=gauges)


def test_served_tokens_are_the_references_argmax(served):
    ref = reference.ServeReference(CFG, 96)
    gaps, _ = ref.gaps(served["w"], served["prompts"], served["streams"])
    assert [len(g) for g in gaps] == [10 + 3 * i for i in range(7)]
    assert max(float(g.max()) for g in gaps) < 1e-4


def test_the_state_gauges_follow_the_slots(served):
    per_slot = 3 * (4 * 8 * 16 * 4 + 3 * 96 * 4)    # float32 tail here
    assert served["engine"].cache.state_bytes_per_slot == per_slot
    slots = [g[0] for g in served["gauges"]]
    assert max(slots) == 3 and slots[-1] == 0
    assert all(g[1] == g[0] * per_slot for g in served["gauges"])
    assert max(g[2] for g in served["gauges"]) > 0
    c = served["counters"]
    # 4 picks a token a layer, of which the held ones are counted
    assert 0 < sum(c[f"serve/expert_assignments/{e}"] for e in range(4))


def test_full_forward_logits_agree_with_the_reference(served):
    tokens = jnp.asarray(served["prompts"][3] + served["streams"][3])
    want = jax.jit(lambda w, t: reference.forward(CFG, w, t))(
        served["w"], tokens)
    for grouped in (True, False):
        model = program(use_grouped_experts=grouped)
        got = jax.jit(model.__call__)(served["w"], tokens)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def _step_fn(model):
    return jax.jit(lambda w, c, t, tab, ln, ids, off: model.forward(
        w, t, kv_cache=c, block_tables=tab, lengths=ln,
        append_block_ids=ids, append_offsets=off))


def _prefill_fn(model, P):
    return jax.jit(lambda w, c, t, r, slot: model.forward(
        w, t, kv_cache=c, block_row=r, prompt_len=P, slot=slot))


def _fresh(model, slots=3):
    cfg = model.cfg
    blocks = {"attention": slots * 12 + 1}
    alloc = KindBlockAllocator(cfg.cache_kinds, blocks, BLOCK, 12, slots)
    cache = KindPagedKVCache.create(cfg.cache_kinds, blocks, 1, BLOCK, 16,
                                    dtype=jnp.float32, max_seqs=slots)
    return alloc, cache


def _prefill(model, w, alloc, cache, prompt, slot, bucket):
    plan = alloc.admit(slot, prompt, bucket // BLOCK)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    row = {k: np.asarray(r, np.int32) for k, r in plan.block_row.items()}
    return _prefill_fn(model, len(prompt))(w, cache, padded, row,
                                           np.int32(slot))


def test_paged_decode_logits_agree_with_the_reference(served):
    """Prefill 13 tokens in a bucket of 16 (shorter than the bucket, no
    multiple of the chunk of 8) into slot 1, then decode 14 more against the
    pool and the state: every step's logits are the reference's row, and
    the idle slots' state rows stay as they were, bit for bit."""
    model, w = served["model"], served["w"]
    seq = served["prompts"][2] + served["streams"][2][:14]
    P = 13
    alloc, cache = _fresh(model)
    # slot 0 holds another request's state, which nobody may touch
    _, cache, _ = _prefill(model, w, alloc, cache, served["prompts"][1], 0,
                           32)
    logits, cache, _ = _prefill(model, w, alloc, cache, seq[:P], 1, 16)
    want = np.asarray(jax.jit(lambda w, t: reference.forward(CFG, w, t))(
        w, jnp.asarray(seq)))
    np.testing.assert_allclose(np.asarray(logits)[0, :P], want[:P], **TOL)
    idle = jax.tree_util.tree_map(lambda a: np.asarray(a[:, 0]),
                                  cache.pools["mamba"])
    step = _step_fn(model)
    active = np.array([False, True, False])
    for pos in range(P, len(seq)):
        assert alloc.prepare_step([1]).failed == []
        ids, off = alloc.append_targets(active)
        tok = np.array([[7], [seq[pos]], [9]], np.int32)
        logits, cache, stats = step(
            w, cache, tok, {k: t.copy() for k, t in alloc.tables.items()},
            alloc.lengths.copy(), ids, off)
        alloc.advance([1])
        np.testing.assert_allclose(np.asarray(logits)[1], want[pos], **TOL)
        assert int(np.asarray(stats)[:, :-1].sum()) <= 3 * 4
    after = cache.pools["mamba"]
    np.testing.assert_array_equal(np.asarray(after.conv[:, 0]), idle.conv)
    np.testing.assert_array_equal(np.asarray(after.ssm[:, 0]), idle.ssm)
    assert float(jnp.abs(after.ssm[:, 2]).max()) == 0.0   # never given


def test_padding_advances_nothing_and_a_slot_given_anew_starts_from_zero(
        served):
    """The state and the conv tail a prefill leaves are those of the
    prompt's LAST REAL token whatever the bucket's padding; and a slot that
    held another request, decoded on, is overwritten whole."""
    model, w = served["model"], served["w"]
    prompt, other = served["prompts"][2], served["prompts"][1]
    alloc, cache = _fresh(model)
    _, cache, _ = _prefill(model, w, alloc, cache, prompt, 0, 16)
    _, cache, _ = _prefill(model, w, alloc, cache, prompt, 1, 32)
    # slot 2: another request first, advanced by decode steps, released
    _, cache, _ = _prefill(model, w, alloc, cache, other, 2, 32)
    step, active = _step_fn(model), np.array([False, False, True])
    for t in (3, 4, 5):
        alloc.prepare_step([2])
        ids, off = alloc.append_targets(active)
        _, cache, _ = step(
            w, cache, np.full((3, 1), t, np.int32),
            {k: v.copy() for k, v in alloc.tables.items()},
            alloc.lengths.copy(), ids, off)
        alloc.advance([2])
    alloc.release(2)
    _, cache, _ = _prefill(model, w, alloc, cache, prompt, 2, 16)
    state = cache.pools["mamba"]
    assert isinstance(state, SlotStateCache)
    assert float(jnp.abs(state.ssm[:, 0]).max()) > 0
    for slot in (1, 2):
        np.testing.assert_allclose(np.asarray(state.ssm[:, slot]),
                                   np.asarray(state.ssm[:, 0]),
                                   atol=1e-5, rtol=1e-5)
        # (another bucket is another program: its float32 sums differ in
        # the last bit)
        np.testing.assert_allclose(np.asarray(state.conv[:, slot]),
                                   np.asarray(state.conv[:, 0]),
                                   atol=1e-5, rtol=1e-5)
    # the tail is the last three REAL inputs of the conv: a prompt of two
    # tokens leaves a zero row before them
    alloc, cache = _fresh(model)
    _, cache, _ = _prefill(model, w, alloc, cache, prompt[:2], 0, 16)
    tail = np.asarray(cache.pools["mamba"].conv[:, 0])
    assert not tail[:, 0].any() and tail[:, 1].any() and tail[:, 2].any()


# -- the scan -----------------------------------------------------------------

def _scan_inputs(T, H=4, P=8, G=2, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (T, H, P)).astype(jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(k[1], (T, H)) - 2.0)
    A = -jnp.exp(jax.random.uniform(k[2], (H,), minval=0.0, maxval=2.7))
    B = jax.random.normal(k[3], (T, G, N)).astype(jnp.bfloat16)
    C = jax.random.normal(k[4], (T, G, N)).astype(jnp.bfloat16)
    return x, dt, A, B, C


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel_interpreted", "xla"])
@pytest.mark.parametrize("T,chunk,length", [
    (32, 8, None), (32, 8, 19), (24, 8, 3), (16, 16, 16), (256, 128, 200)])
def test_the_chunked_scan_is_the_token_by_token_recurrence(T, chunk, length,
                                                           use_pallas):
    """Both forms of the chunked algorithm against the recurrence as
    written, for lengths that are no multiple of the chunk: y at the real
    positions and the state of the last real token. Float32 sums in
    another order: 1e-5 of values of order 10 (2e-4 of 100 at 256
    tokens)."""
    x, dt, A, B, C = _scan_inputs(
        T, *((4, 64, 2, 128) if chunk == 128 else ()))
    want_y, want_h = mamba2.mamba2_recurrence(x, dt, A, B, C, length=length)
    y, h = mamba2.mamba2_chunk_scan(x, dt, A, B, C, chunk=chunk,
                                    length=length, use_pallas=use_pallas)
    n = T if length is None else length
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(np.asarray(y[:n]), np.asarray(want_y[:n]),
                               atol=1e-5 * scale, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h),
                               atol=1e-5 * scale, rtol=1e-5)


def test_the_scan_pads_what_is_no_whole_chunk():
    x, dt, A, B, C = _scan_inputs(20)
    want_y, want_h = mamba2.mamba2_recurrence(x, dt, A, B, C)
    y, h = mamba2.mamba2_chunk_scan(x, dt, A, B, C, chunk=8)
    assert y.shape == (20, 4, 8)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=1e-4)
    assert mamba2.supports_chunk_scan(2048, 128, 128)
    assert not mamba2.supports_chunk_scan(32, 8, 16)


def test_one_decode_update_is_one_step_of_the_recurrence():
    x, dt, A, B, C = _scan_inputs(9)
    _, before = mamba2.mamba2_recurrence(x[:8], dt[:8], A, B[:8], C[:8])
    want_y, want_h = mamba2.mamba2_recurrence(x, dt, A, B, C)
    state = jnp.stack([before, before])
    y, new = mamba2.mamba2_decode_update(
        state, jnp.stack([x[8]] * 2), jnp.stack([dt[8]] * 2), A,
        jnp.stack([B[8]] * 2), jnp.stack([C[8]] * 2),
        valid=jnp.asarray([True, False]))
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want_y[8]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new[0]), np.asarray(want_h),
                               atol=1e-6, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(before))


# -- the expert layer's forms -------------------------------------------------

@pytest.mark.parametrize("form", ["relu2_latent_shared", "swiglu_riding"])
def test_one_held_experts_layer_in_both_forms(form):
    """ONE class: the squared-ReLU form (one up matrix, a selection bias, a
    scaling factor, a latent round the routed part, a shared expert of its
    own width) and the gated form with riding shared experts, each through
    the sorted grouped kernels (interpreted) against experts x tokens."""
    kw = dict(activation="relu2", select_bias=True, scaling=5.0,
              latent_size=32, shared_size=96) \
        if form == "relu2_latent_shared" else dict(num_shared=2)
    out = {}
    for grouped in (True, False):
        layer = HeldExpertsMLP(64, 48, 16, 4, held=(2, 5, 6, 11),
                               params_dtype=jnp.float32, init_std=0.3,
                               use_pallas=grouped, **kw)
        params = layer.init(jax.random.PRNGKey(1))
        x = jax.random.normal(jax.random.PRNGKey(2), (40, 64), jnp.float32)
        valid = jnp.arange(40) < 33
        out[grouped], stats = layer(params, x, valid=valid)
        assert int(stats["load"].sum()) <= 33 * 4
    assert layer.stacked == (("w_up", "w_down") if "relu2" in form
                             else ("w_gate", "w_up", "w_down"))
    np.testing.assert_allclose(np.asarray(out[True]), np.asarray(out[False]),
                               atol=2e-3, rtol=2e-3)
    with pytest.raises(ValueError, match="not both"):
        HeldExpertsMLP(64, 48, 16, 4, held=(1,), num_shared=1,
                       shared_size=8)


def test_the_selection_bias_moves_the_choice_and_not_the_weights():
    layer = HeldExpertsMLP(64, 48, 16, 4, held=tuple(range(16)),
                           select_bias=True, scaling=5.0)
    router = jax.random.normal(jax.random.PRNGKey(0), (64, 16)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(1), (20, 64))
    bias = jnp.zeros((16,)).at[3].set(10.0)        # expert 3 always chosen
    local, weight = layer.route(router, x, bias=bias)
    plain, _ = layer.route(router, x)
    assert bool(jnp.all(jnp.any(local == 3, axis=1)))
    assert not bool(jnp.all(jnp.any(plain == 3, axis=1)))
    # the weights are the picks' own scores, normalised, times the factor
    np.testing.assert_allclose(np.asarray(weight.sum(-1)), 5.0, rtol=1e-5)
    scores = jax.nn.sigmoid(x @ router)
    picked = jnp.take_along_axis(scores, local, axis=1)
    np.testing.assert_allclose(
        np.asarray(weight),
        np.asarray(5.0 * picked / picked.sum(-1, keepdims=True)), rtol=1e-4)


# -- the shares of four chips -------------------------------------------------

UNCUT = dict(CFG, mamba_num_heads=16, n_groups=8, num_attention_heads=16,
             num_key_value_heads=4, n_routed_experts=16, first_held_expert=0)


def _columns(blocks, chip, of=4):
    """The chip's quarter of each block of columns."""
    out = []
    start = 0
    for width in blocks:
        q = width // of
        out.append(np.arange(start + chip * q, start + (chip + 1) * q))
        start += width
    return np.concatenate(out)


def _share(kind, lp, chip):
    """What chip ``chip`` of four holds of one uncut layer."""
    if kind == "attention":
        return dict(lp, wq=lp["wq"][:, _columns([256], chip)],
                    wk=lp["wk"][:, _columns([64], chip)],
                    wv=lp["wv"][:, _columns([64], chip)],
                    wo=lp["wo"][_columns([256], chip)])
    if kind == "moe":
        held = slice(4 * chip, 4 * chip + 4)
        return dict(lp, w_up=lp["w_up"][held], w_down=lp["w_down"][held])
    inner, gn, nh = 128, 8 * 16, 16          # z | x | B | C | dt
    conv = _columns([inner, gn, gn], chip)
    return dict(
        lp, in_proj=lp["in_proj"][:, _columns([inner, inner, gn, gn, nh],
                                              chip)],
        conv_w=lp["conv_w"][conv], conv_b=lp["conv_b"][conv],
        dt_bias=lp["dt_bias"][_columns([nh], chip)],
        A_log=lp["A_log"][_columns([nh], chip)],
        D=lp["D"][_columns([nh], chip)],
        gate_norm=lp["gate_norm"][_columns([inner], chip)],
        out_proj=lp["out_proj"][_columns([inner], chip)])


@pytest.mark.parametrize("letter", ["M", "E", "*"])
def test_the_four_shares_add_up_to_the_uncut_layer(letter):
    """The parts four chips' PROGRAMS give for one layer (4 Mamba heads
    with their 2 groups; 4 query heads on 1 KV head; 4 of 16 experts),
    with what every chip computes alike (the shared expert; the residual)
    counted once, are the uncut REFERENCE's layer: the gated norm is a
    group's own and the latent's up-projection is linear, so nothing of
    another chip is needed before the sum."""
    kind = reference.KIND_OF[letter]
    uncut = dict(UNCUT, hybrid_override_pattern=letter, num_hidden_layers=1)
    w = weights(uncut)
    lp = {n: v[0] for n, v in w["layers"][kind].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    want = reference.layer(uncut, lp, x, kind, False)
    total = x
    for chip in range(4):
        cut = dict(CFG, hybrid_override_pattern=letter, num_hidden_layers=1,
                   first_held_expert=4 * chip)
        model = program(cut)
        share = _share(kind, lp, chip)
        big = {n: share.pop(n)[None] for n in model.experts.stacked
               if n in share}
        part, _, _ = model._prefill_layer(kind, share, big, 0, x, None, None,
                                          None)
        total = total + (part - x)
    if kind == "moe":
        u = reference.norm(x, lp["norm"], 1e-5)
        total = total - 3 * reference.expert_parts(uncut, lp, u, False)[1]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4, rtol=1e-4)

"""The pattern decoder as a Jamba hybrid (Mamba-1 layers with a lane-major
per-slot state beside the paged KV pool, attention without positions, two
sub-blocks a layer with a dense gated MLP) against the plain reference of
``benchmark/families/jamba_reference.py``, at a size the CPU holds: hidden
64, 6 layers with attention at 2 and 5, 128 Mamba channels with a state of
16, a dt rank of 8 and scan chunks of 8, 4 query heads on 1 KV head of 16, an
MLP of 96, block 8. The weights are the reference's (bfloat16-stored); the
program computes in float32 here so that agreement is tight: what is left is
the order of float32 sums (the chunked scan against the token-by-token
recurrence, the flash softmax against the plain one), 3e-4 of logits of
order 1. The fp8 control and each planted fault move the logits by 8e-3 or
more (the state rounded to bfloat16 the least), an order over the
tolerance."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.pattern_decoder import LAYER_KINDS, PatternDecoder
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.ops import mamba1
from apex_tpu.serving import Request, ServingEngine, SlotScheduler
from apex_tpu.serving.cache import (KindBlockAllocator, KindPagedKVCache,
                                    SlotStateCache, StateSpec)
from benchmark.families import jamba as family

reference = family.reference
MAMBA, ATTENTION = reference.MAMBA, reference.ATTENTION

CFG = {
    "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 6, "attn_layer_offset": 2, "attn_layer_period": 3,
    "expert_layer_offset": 1, "expert_layer_period": 2, "num_experts": 1,
    "num_experts_per_tok": 1, "num_attention_heads": 4,
    "num_key_value_heads": 1, "mamba_expand": 2, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_dt_rank": 8, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 256, "initializer_range": 0.1,
    "scan_chunk": 8,
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


def test_the_two_kinds_are_a_mixer_then_a_dense_mlp():
    model = program()
    cfg = model.cfg
    assert cfg.layer_types == (MAMBA, MAMBA, ATTENTION) * 2
    assert cfg.block == "sequential" and cfg.tie_embeddings
    assert LAYER_KINDS[MAMBA].feed_forward == "dense" \
        and LAYER_KINDS[ATTENTION].mixer == "attention"
    kinds = cfg.cache_kinds
    assert list(kinds) == [MAMBA, ATTENTION]
    assert kinds[ATTENTION] == (2, None)
    # the state kind takes its shape from the model: channels last
    assert kinds[MAMBA] == StateSpec(4, 128, 4, 1, 128, 16,
                                     layout="channels_last")
    assert kinds[MAMBA].slot_shape == (16, 128)
    # no expert layer: nothing built for one, nothing counted, nothing
    # packed behind the sampled tokens
    assert model.experts is None and not model.step_stats
    assert model.stats_shape == (0, 1)
    names = set(model.param_shapes()["layers"][MAMBA])
    assert "router" not in names and {"mlp_gate", "mlp_up", "mlp_down",
                                      "ff_norm", "dt_norm", "b_norm",
                                      "c_norm"} <= names
    assert jax.tree_util.tree_map(lambda x: x.shape, weights()) \
        == model.param_shapes()
    with pytest.raises(ValueError, match="a mixer then a feed-forward"):
        dataclasses.replace(cfg, layer_types=("mamba",))
    with pytest.raises(ValueError, match="one sub-block"):
        dataclasses.replace(cfg, block="prenorm")


def test_the_published_rule_puts_attention_at_layers_7_and_21():
    from benchmark import run as harness
    cfg = harness.load_json(harness.HERE, "configs", "jamba2-3b.json")
    types = reference.layer_types(cfg)
    assert [i for i, t in enumerate(types) if t == ATTENTION] == [7, 21]
    assert len(types) == 28 and types.count(MAMBA) == 26
    model = family.model(cfg)
    assert len(model.cfg.period) == 14
    assert model.cfg.cache_kinds[MAMBA].slot_shape == (16, 5120)
    with pytest.raises(ValueError, match="num_experts 1"):
        reference.layer_types(dict(cfg, num_experts=16))


@pytest.fixture(scope="module")
def served():
    """Seven prompts, shorter than their bucket and no multiple of the
    chunk, through the scheduler over the paged engine on THREE slots: they
    are admitted at different steps and every slot is given anew after a
    release."""
    model, w = program(), weights()
    engine = ServingEngine(
        model, w, max_seqs=3, max_len=96, prefill_len=[16, 32],
        block_size=BLOCK, cache_dtype=jnp.float32,
        num_blocks={ATTENTION: 3 * 12 + 1})
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
                       snap.get("serve/state_bytes_held")))
    done = {c.request_id: c for c in sched.drain_completed()}
    return dict(model=model, w=w, engine=engine, prompts=prompts,
                lengths=lengths,
                streams=[list(done[i].tokens) for i in range(len(prompts))],
                counters=registry.snapshot(), gauges=gauges)


def test_served_tokens_are_the_references_argmax(served):
    ref = reference.ServeReference(CFG, 96)
    gaps, _ = ref.gaps(served["w"], served["prompts"], served["streams"])
    assert [len(g) for g in gaps] == [10 + 3 * i for i in range(7)]
    assert max(float(g.max()) for g in gaps) < 1e-4


def test_the_state_gauges_count_the_new_kind_by_its_true_bytes(served):
    # (16, 128) float32 a layer and a float32 tail here: no padded lanes
    per_slot = 4 * (16 * 128 * 4 + 3 * 128 * 4)
    engine = served["engine"]
    assert engine.cache.state_bytes_per_slot == per_slot
    assert engine.cache.pools[MAMBA].ssm.shape == (4, 3, 16, 128)
    slots = [g[0] for g in served["gauges"]]
    assert max(slots) == 3 and slots[-1] == 0
    assert all(g[1] == g[0] * per_slot for g in served["gauges"])
    # the engine fetched tokens alone, and the scheduler counted no expert
    assert engine.last_stats is None and engine.stats_names == ()
    assert not [n for n in served["counters"] if "expert" in n]


def test_the_prefill_counters_tell_a_prompts_tokens_from_its_buckets(served):
    c = served["counters"]
    assert c["serve/prefill_tokens"] == sum(served["lengths"])
    buckets = sum(16 if n <= 16 else 32 for n in served["lengths"])
    assert c["serve/prefill_bucket_tokens"] == buckets
    assert served["engine"].bucket_of(17) == 32
    with pytest.raises(ValueError, match="exceeds the prefill window"):
        served["engine"].bucket_of(33)


def test_full_forward_logits_agree_with_the_reference(served):
    tokens = jnp.asarray(served["prompts"][3] + served["streams"][3])
    want = jax.jit(lambda w, t: reference.forward(CFG, w, t))(
        served["w"], tokens)
    got = jax.jit(served["model"].__call__)(served["w"], tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("mode", ("fp8",) + reference.FAULTS)
def test_the_control_and_each_planted_fault_break_the_tolerance(served,
                                                                mode):
    """The reference in fp8, and with each fault of arithmetic planted, is
    NOT within the tolerance the program holds: a program that computed so
    would fail ``test_full_forward_logits_agree_with_the_reference``."""
    tokens = jnp.asarray(served["prompts"][3] + served["streams"][3])
    fwd = jax.jit(lambda w, t, mode: reference.forward(
        CFG, w, t, mode, seam=len(served["prompts"][3])),
        static_argnums=2)
    want, off = fwd(served["w"], tokens, False), \
        fwd(served["w"], tokens, mode)
    assert float(jnp.abs(off - want).max()) > 20 * TOL["atol"]
    with pytest.raises(AssertionError):
        np.testing.assert_allclose(np.asarray(off), np.asarray(want), **TOL)


def _step_fn(model):
    return jax.jit(lambda w, c, t, tab, ln, ids, off: model.forward(
        w, t, kv_cache=c, block_tables=tab, lengths=ln,
        append_block_ids=ids, append_offsets=off))


def _prefill_fn(model, P):
    return jax.jit(lambda w, c, t, r, slot: model.forward(
        w, t, kv_cache=c, block_row=r, prompt_len=P, slot=slot))


def _fresh(model, slots=3):
    cfg = model.cfg
    blocks = {ATTENTION: slots * 12 + 1}
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
    """The seam: prefill 13 tokens in a bucket of 16 (shorter than the
    bucket, no multiple of the chunk of 8) into slot 1, then decode 14 more
    against the pool, the conv tail and the state the prefill left: every
    step's logits are the reference's row, and the idle slots' state rows
    stay as they were, bit for bit."""
    model, w = served["model"], served["w"]
    seq = served["prompts"][2] + served["streams"][2][:14]
    P = 13
    alloc, cache = _fresh(model)
    # slot 0 holds another request's state, which nobody may touch
    _, cache, _ = _prefill(model, w, alloc, cache, served["prompts"][1], 0,
                           32)
    logits, cache, stats = _prefill(model, w, alloc, cache, seq[:P], 1, 16)
    assert stats.shape == (0, 1)
    want = np.asarray(jax.jit(lambda w, t: reference.forward(CFG, w, t))(
        w, jnp.asarray(seq)))
    np.testing.assert_allclose(np.asarray(logits)[0, :P], want[:P], **TOL)
    held = cache.pools[MAMBA]
    idle = SlotStateCache(np.asarray(held.conv[:, :, 0]),
                          np.asarray(held.ssm[:, 0]))
    step = _step_fn(model)
    active = np.array([False, True, False])
    for pos in range(P, len(seq)):
        assert alloc.prepare_step([1]).failed == []
        ids, off = alloc.append_targets(active)
        tok = np.array([[7], [seq[pos]], [9]], np.int32)
        logits, cache, _ = step(
            w, cache, tok, {k: t.copy() for k, t in alloc.tables.items()},
            alloc.lengths.copy(), ids, off)
        alloc.advance([1])
        np.testing.assert_allclose(np.asarray(logits)[1], want[pos], **TOL)
    after = cache.pools[MAMBA]
    np.testing.assert_array_equal(np.asarray(after.conv[:, :, 0]), idle.conv)
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
    state = cache.pools[MAMBA]
    assert isinstance(state, SlotStateCache)
    assert float(jnp.abs(state.ssm[:, 0]).max()) > 0
    for slot in (1, 2):
        # (another bucket is another program: its float32 sums differ in
        # the last bit)
        np.testing.assert_allclose(np.asarray(state.ssm[:, slot]),
                                   np.asarray(state.ssm[:, 0]),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(state.conv[:, :, slot]),
                                   np.asarray(state.conv[:, :, 0]),
                                   atol=1e-5, rtol=1e-5)
    # the tail is the last three REAL inputs of the conv: a prompt of two
    # tokens leaves a zero row before them
    alloc, cache = _fresh(model)
    _, cache, _ = _prefill(model, w, alloc, cache, prompt[:2], 0, 16)
    tail = np.asarray(cache.pools[MAMBA].conv[:, :, 0])    # (layers, 3, E)
    assert not tail[:, 0].any() and tail[:, 1].any() and tail[:, 2].any()


# -- the scan -----------------------------------------------------------------

def _scan_inputs(T, E=1024, N=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    u = jax.random.normal(k[0], (T, E)).astype(jnp.bfloat16)
    delta = jax.nn.softplus(jax.random.normal(k[1], (T, E)) - 3.0)
    A = -jnp.arange(1, N + 1, dtype=jnp.float32)[:, None] \
        * jnp.exp(0.2 * jax.random.normal(k[2], (N, E)))
    B = jax.random.normal(k[3], (T, N))
    C = jax.random.normal(k[4], (T, N))
    return u, delta, A, B, C


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel_interpreted", "xla"])
@pytest.mark.parametrize("T,chunk,length", [
    (32, 16, None), (32, 16, 19), (48, 16, 3), (16, 16, 16), (64, 32, 41),
    (24, 8, 8)])
def test_the_selective_scan_is_the_token_by_token_recurrence(T, chunk, length,
                                                             use_pallas):
    """Both chunked forms against the recurrence as written, for lengths
    that are, and are not, whole chunks (and no whole trips of the kernel's
    loop): y at the real positions and the state of the last real token.
    Float32 sums in another order, and the kernel's 2^(x log2 e) for e^x:
    1e-5 of values of order 1."""
    u, delta, A, B, C = _scan_inputs(T)
    want_y, want_h = mamba1.mamba1_recurrence(u, delta, A, B, C,
                                              length=length)
    y, h = mamba1.mamba1_selective_scan(u, delta, A, B, C, chunk=chunk,
                                        length=length, use_pallas=use_pallas)
    n = T if length is None else length
    assert y.shape == (T, 1024) and h.shape == (16, 1024)
    scale = float(jnp.abs(want_y).max())
    np.testing.assert_allclose(np.asarray(y[:n]), np.asarray(want_y[:n]),
                               atol=1e-5 * scale, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h),
                               atol=1e-5 * scale, rtol=1e-5)
    assert bool(jnp.all(jnp.isfinite(y)))


def test_the_scan_pads_what_is_no_whole_chunk_and_gates_the_kernel():
    u, delta, A, B, C = _scan_inputs(20, E=128)
    want_y, want_h = mamba1.mamba1_recurrence(u, delta, A, B, C)
    # 128 channels are no whole 1,024-channel block: the gate picks XLA
    y, h = mamba1.mamba1_selective_scan(u, delta, A, B, C, chunk=8)
    assert y.shape == (20, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(want_h), atol=1e-5)
    assert mamba1.supports_selective_scan(1024, 128, 5120)
    assert not mamba1.supports_selective_scan(32, 8, 128)
    assert not mamba1.supports_selective_scan(100, 128, 5120)
    with pytest.raises(ValueError, match="do not sit on"):
        mamba1.mamba1_selective_scan(u[:16], delta[:16], A, B[:16], C[:16],
                                     chunk=8, use_pallas=True)


@pytest.mark.parametrize("use_pallas", [True, False],
                         ids=["kernel_interpreted", "xla"])
def test_one_decode_update_is_one_step_of_the_recurrence(use_pallas):
    """Eight slots at layer 1 of three: slot ``s`` holds the state after
    ``s + 1`` tokens and takes token ``s + 1``; the odd slots are idle. A
    served slot's rows and read-out are the recurrence's next step, an idle
    slot's rows and the other layers stay as they were, bit for bit."""
    u, delta, A, B, C = _scan_inputs(9, E=256)
    want_y, _ = mamba1.mamba1_recurrence(u, delta, A, B, C)
    after = [mamba1.mamba1_recurrence(u[:n], delta[:n], A, B[:n], C[:n])[1]
             for n in range(1, 10)]
    rows = jnp.stack(after[:8])
    states = jnp.stack([rows + 1.0, rows, rows - 1.0])
    valid = jnp.arange(8) % 2 == 0
    y, new = jax.jit(lambda st: mamba1.mamba1_decode_update(
        st, jnp.int32(1), u[1:9], delta[1:9], A, B[1:9], C[1:9], valid,
        use_pallas=use_pallas))(states)
    for s in range(0, 8, 2):
        np.testing.assert_allclose(np.asarray(y[s]), np.asarray(want_y[s + 1]),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(new[1, s]),
                                   np.asarray(after[s + 1]),
                                   atol=1e-6, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(new[1, 1::2]),
                                  np.asarray(rows[1::2]))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(states[0]))
    np.testing.assert_array_equal(np.asarray(new[2]), np.asarray(states[2]))
    assert mamba1.supports_decode_update(128, 5120)
    assert not mamba1.supports_decode_update(3, 128)


def test_the_decode_step_moves_the_state_where_it_lies():
    """``jit_decode_step`` over a donated cache aliases every cache leaf to
    its result: the state rows are updated in place, not copied."""
    model = program()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    _, cache = _fresh(model)
    S = 3
    args = (params, cache, jnp.zeros((S, 1), jnp.int32),
            {ATTENTION: jnp.zeros((S, 12), jnp.int32)},
            jnp.zeros((S,), jnp.int32), {ATTENTION: jnp.zeros((S,), jnp.int32)},
            jnp.zeros((S,), jnp.int32))
    fn = lambda w, c, t, tab, ln, ids, off: model.forward(
        w, t, kv_cache=c, block_tables=tab, lengths=ln,
        append_block_ids=ids, append_offsets=off)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes


# -- the state kind's two layouts ---------------------------------------------

def test_a_state_spec_shapes_the_rows_as_its_model_says():
    heads_last = StateSpec(3, 96, 4, 4, 8, 16)
    lanes = StateSpec(3, 96, 4, 1, 128, 16, layout="channels_last")
    assert heads_last.layout == "state_last"
    assert SlotStateCache.create(heads_last, 5).ssm.shape == (3, 5, 4, 8, 16)
    held = SlotStateCache.create(lanes, 5)
    assert held.ssm.shape == (3, 5, 16, 128) and held.ssm.dtype == jnp.float32
    assert held.conv.shape == (3, 3, 5, 96)        # slots second to last
    assert held.bytes_per_slot == 3 * (16 * 128 * 4 + 3 * 96 * 2)
    new = held.write_slot(1, 2, jnp.ones((3, 96)), jnp.ones((16, 128)))
    assert float(new.ssm[1, 2].min()) == 1.0 and float(new.ssm.sum()) == 2048
    with pytest.raises(ValueError, match="state layout"):
        StateSpec(3, 96, 4, 1, 128, 16, layout="rows").slot_shape


def test_prefix_sharing_and_speculation_go_on_refusing_a_by_kind_model():
    model, w = program(), weights()
    kw = dict(max_seqs=2, max_len=32, prefill_len=[16], block_size=BLOCK,
              cache_dtype=jnp.float32, num_blocks={ATTENTION: 9})
    for bad in (dict(speculate_k=2), dict(prefix_suffix_cap=4)):
        with pytest.raises(ValueError):
            ServingEngine(model, w, **kw, **bad)

"""The pattern decoder as Mistral Small 4 (latent attention with a latent
paged cache and an absorbed decode kernel, YaRN positions with llama 4's
query scale, a sequential block whose feed-forward is the expert layer with
a selection bias and one shared expert) against the plain reference of
``benchmark/families/mistral4_reference.py``, at a size the CPU holds
(``tests/benchmark/cells/configs/tiny-mistral4.json``): hidden 64, 2
layers, 4 heads of 8 + 8 query-key and 16 value, latents of 32 (query) and
16 (key-value), 4 held experts of a router of 16 at top-4, block 8. Its
YaRN table stretches 16 positions (factor 4), so prompts and answers cross
the ramp and llama 4's scale steps at 16 and 32. The weights are the
reference's (bfloat16-stored); the program computes in float32 here so that
agreement is tight: what is left is the order of float32 sums (the flash
softmax and the absorbed kernel against one plain softmax over the expanded
heads), 2e-5 of logits of order 3. The fp8 control and each planted fault
move the logits by 0.2 or more, four orders over the tolerance."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.pattern_decoder import LAYER_KINDS, PatternDecoder
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.ops import mla
from apex_tpu.ops.flash_attention import paged_work_list
from apex_tpu.serving import Request, ServingEngine, SlotScheduler
from apex_tpu.serving.cache import (KindBlockAllocator, KindPagedKVCache,
                                    LatentPagedCache, LatentSpec)
from benchmark import run as harness
from benchmark.families import mistral4 as family

reference = family.reference
KIND = reference.KIND
CFG = harness.load_json(harness.ROOT, "tests", "benchmark", "cells",
                        "configs", "tiny-mistral4.json")
BLOCK = 8
TOL = dict(atol=2e-5, rtol=2e-5)


def program(cfg=CFG, **over):
    model = family.model(cfg)
    return PatternDecoder(dataclasses.replace(
        model.cfg, compute_dtype=jnp.float32, **over))


def weights(cfg=CFG, seed=5):
    lo, hi = reference.seed_key(seed)
    return jax.jit(lambda lo, hi: reference.make_weights(cfg, lo, hi))(lo, hi)


def test_the_layer_is_latent_attention_then_the_expert_layer():
    model = program()
    cfg = model.cfg
    assert cfg.layer_types == (KIND, KIND) and cfg.block == "sequential"
    assert LAYER_KINDS[KIND].mixer == "mla" \
        and LAYER_KINDS[KIND].feed_forward == "experts" \
        and LAYER_KINDS[KIND].cache == "latent"
    # ONE row a position for all four heads: the latent and the
    # positional key
    assert cfg.cache_kinds == {KIND: LatentSpec(2, 16, 8)}
    experts = model.experts
    assert experts.select_bias and experts.shared_size == 32 \
        and experts.num_shared == 0 and experts.top_k == 4 \
        and experts.num_experts == 16 and experts.held == (0, 1, 2, 3)
    assert model.step_stats and model.stats_shape == (2, 5)
    assert jax.tree_util.tree_map(lambda x: x.shape, weights()) \
        == model.param_shapes()
    # m = 0.1 ln 4 + 1, squared, over sqrt(16)
    assert model.mla_scale == pytest.approx(
        (0.1 * np.log(4.0) + 1.0) ** 2 / 4.0)
    with pytest.raises(ValueError, match="v_head_dim"):
        dataclasses.replace(cfg, v_head_dim=8)
    with pytest.raises(ValueError, match="a mixer then a feed-forward"):
        dataclasses.replace(cfg, layer_types=("moe",))


def test_the_yarn_table_is_deepseeks_at_the_published_sizes():
    """At the published 64-wide positional part (theta 10,000, factor 128,
    8,192 positions, beta 32 / 1) the correction range is dimensions 12 to
    25: the first twelve frequencies are kept, from the 25th on they are
    divided by 128, between them a linear ramp; program and reference
    compute it apart and agree."""
    cfg = harness.load_json(harness.HERE, "configs",
                            "mistral-small-4-119b.json")
    inv = mla.yarn_inv_freq(64, 10000.0, 128.0, 8192, 32.0, 1.0)
    f = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:13], f[:13], rtol=1e-6)
    np.testing.assert_allclose(inv[25:], f[25:] / 128, rtol=1e-6)
    r = (18 - 12) / (25 - 12)
    assert inv[18] == pytest.approx(f[18] / 128 * r + f[18] * (1 - r),
                                    rel=1e-6)
    np.testing.assert_array_equal(inv, reference.inv_freq(cfg))
    assert mla.yarn_mscale(128.0, 1.0) == pytest.approx(1.48520, abs=1e-5)
    assert family.model(cfg).mla_scale == pytest.approx(
        1.48520 ** 2 / 128 ** 0.5, rel=1e-5)
    np.testing.assert_array_equal(mla.yarn_inv_freq(8, 100.0),
                                  (100.0 ** (-np.arange(0, 8, 2) / 8)
                                   ).astype(np.float32))


@pytest.fixture(scope="module")
def served():
    """Seven prompts, some past the 16 positions YaRN stretches, through the
    scheduler over the paged engine on THREE slots: admitted at different
    steps, every slot given anew after a release."""
    model, w = program(), weights()
    engine = ServingEngine(
        model, w, max_seqs=3, max_len=96, prefill_len=[16, 32],
        block_size=BLOCK, cache_dtype=jnp.float32,
        num_blocks={KIND: 3 * 12 + 1})
    registry = MetricsRegistry()
    sched = SlotScheduler(engine, registry=registry)
    rng = np.random.default_rng(0)
    lengths = (5, 20, 13, 32, 3, 9, 27)
    prompts = [rng.integers(1, 128, n).tolist() for n in lengths]
    reads = []
    for i, p in enumerate(prompts):
        sched.submit(Request(prompt=p, max_new_tokens=10 + 3 * i,
                             temperature=0.0, request_id=i))
    while sched.pending:
        before = engine.latent_positions_read
        sched.step()
        reads.append(engine.latent_positions_read - before)
    done = {c.request_id: c for c in sched.drain_completed()}
    return dict(model=model, w=w, engine=engine, prompts=prompts,
                lengths=lengths, reads=reads,
                streams=[list(done[i].tokens) for i in range(len(prompts))],
                counters=registry.snapshot())


def test_served_tokens_are_the_references_argmax(served):
    ref = reference.ServeReference(CFG, 96)
    gaps, _ = ref.gaps(served["w"], served["prompts"], served["streams"])
    assert [len(g) for g in gaps] == [10 + 3 * i for i in range(7)]
    assert max(float(g.max()) for g in gaps) < 1e-4


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
    would fail the comparisons above. The prompt is 32 long, so llama 4's
    scale has stepped and the served rows read the cache."""
    tokens = jnp.asarray(served["prompts"][3] + served["streams"][3])
    fwd = jax.jit(lambda w, t, mode: reference.forward(
        CFG, w, t, mode, seam=len(served["prompts"][3])), static_argnums=2)
    want, off = fwd(served["w"], tokens, False), \
        fwd(served["w"], tokens, mode)
    assert float(jnp.abs(off - want).max()) > 1000 * TOL["atol"]


def _fresh(model, slots=3):
    cfg = model.cfg
    blocks = {KIND: slots * 12 + 1}
    alloc = KindBlockAllocator(cfg.cache_kinds, blocks, BLOCK, 12, slots)
    cache = KindPagedKVCache.create(cfg.cache_kinds, blocks, 4, BLOCK, 16,
                                    dtype=jnp.float32, max_seqs=slots)
    return alloc, cache


def _prefill(model, w, alloc, cache, prompt, slot, bucket):
    plan = alloc.admit(slot, prompt, bucket // BLOCK)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    row = {k: np.asarray(r, np.int32) for k, r in plan.block_row.items()}
    return jax.jit(lambda w, c, t, r: model.forward(
        w, t, kv_cache=c, block_row=r, prompt_len=len(prompt)))(
            w, cache, padded, row)


@pytest.mark.parametrize("use_flash", [None, False],
                         ids=["kernel_interpreted", "xla"])
def test_paged_decode_logits_agree_with_the_reference(served, use_flash):
    """The seam: prefill 13 tokens in a bucket of 16 into slot 1 (slot 0
    holds another request), then decode 27 more through the latent cache,
    past the 16 and the 32 positions where llama 4's scale steps: every
    step's logits are the reference's row, the absorbed kernel's and its
    XLA form's alike."""
    model = program(use_flash=use_flash)
    w = served["w"]
    seq = served["prompts"][2] + served["streams"][2][:27]
    P = 13
    alloc, cache = _fresh(model)
    _, cache, _ = _prefill(model, w, alloc, cache, served["prompts"][1], 0,
                           32)
    logits, cache, stats = _prefill(model, w, alloc, cache, seq[:P], 1, 16)
    assert stats.shape == (2, 5)
    want = np.asarray(jax.jit(lambda w, t: reference.forward(CFG, w, t))(
        w, jnp.asarray(seq)))
    np.testing.assert_allclose(np.asarray(logits)[0, :P], want[:P], **TOL)
    step = jax.jit(lambda w, c, t, tab, ln, ids, off: model.forward(
        w, t, kv_cache=c, block_tables=tab, lengths=ln,
        append_block_ids=ids, append_offsets=off))
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
    # slot 1's blocks hold a row at every position it has seen, each layer,
    # its positional key with the positions on the lanes
    pool = cache.pools[KIND]
    table = alloc.tables[KIND][1][:-(-len(seq) // BLOCK)]
    latent = np.asarray(pool.latent)[:, table].reshape(2, -1, 16)
    rope = np.asarray(pool.rope)[:, table].transpose(0, 1, 3, 2).reshape(
        2, -1, 8)
    for part in (latent, rope):
        assert np.count_nonzero(np.abs(part[:, :len(seq)]).sum(-1)) \
            == 2 * len(seq)


def _kernel_inputs(loads, latent=256, rope=64, heads=32, bs=128, seed=0):
    """The two leaves of a pool of ragged slots: slot ``s`` holds
    ``loads[s]`` positions (0: an idle slot) in blocks spread over layer 1
    of 2."""
    S = len(loads)
    n_blocks = max(-(-n // bs) for n in loads) + 1
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    nb = S * n_blocks + 1
    bf = lambda key, shape: jax.random.normal(key, shape).astype(jnp.bfloat16)
    pools = (bf(k[0], (2, nb, bs, latent)), bf(k[1], (2, nb, rope, bs)))
    q = (bf(k[2], (S, heads, latent)), bf(k[3], (S, heads, rope)))
    perm = np.random.default_rng(seed).permutation(S * n_blocks) + 1
    tables = perm.reshape(S, n_blocks).astype(np.int32)
    return q, pools, tables, np.asarray(loads, np.int32)


@pytest.mark.parametrize("loads", [
    (1, 129, 0, 300, 128, 7), (0, 0, 5), (256,), (0, 1, 0, 2, 0, 255)])
def test_the_absorbed_kernel_is_its_xla_form(loads):
    """The kernel (interpreted here) against its XLA form on ragged slots:
    one position, a block and one more, idle slots, whole blocks. An idle
    slot reads a context of 0. Float32 sums in another order over bfloat16
    rows: 1e-2 of contexts of order 0.1, i.e. one bfloat16 rounding of the
    output."""
    q, pools, tables, lengths = _kernel_inputs(loads)
    scale = 1.48520 ** 2 / 128 ** 0.5
    fn = lambda use: jax.jit(lambda q, p, t, n: mla.mla_decode_attention(
        *q, *p, 1, t, n, softmax_scale=scale, use_pallas=use))(
            q, pools, tables, lengths)
    got, want = fn(None), fn(False)
    assert got.shape == (len(loads), 32, 256) and got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-2, rtol=1e-2)
    idle = lengths == 0
    assert not np.asarray(got, np.float32)[idle].any()
    # the walk visits a slot's live blocks only
    work = paged_work_list(lengths, 128, tables.shape[1])
    assert int(work.n_live) == int(sum(-(-n // 128) for n in loads))


def test_the_latent_pool_keeps_a_row_in_two_lane_dense_leaves():
    """A row ``[c | k_pe]`` lands in the latent leaf token-major and in the
    positional leaf with the positions on the lanes, from a prefill's whole
    blocks and from a decode step's append alike; 2 bytes a value and no
    padding."""
    pool = LatentPagedCache.create(LatentSpec(2, 16, 8), 5, 8)
    assert pool.latent.shape == (2, 5, 8, 16) \
        and pool.rope.shape == (2, 5, 8, 8)
    assert pool.nbytes() == 2 * 5 * 8 * 24 * 2 and pool.block_size == 8
    rows = jnp.arange(16 * 24, dtype=jnp.float32).reshape(16, 24)
    pool = pool.write_layer_blocks(1, rows, jnp.asarray([3, 0]))
    np.testing.assert_array_equal(np.asarray(pool.latent[1, 3], np.float32),
                                  np.asarray(rows[:8, :16]))
    np.testing.assert_array_equal(np.asarray(pool.rope[1, 3], np.float32),
                                  np.asarray(rows[:8, 16:]).T)
    new = jnp.arange(48, dtype=jnp.float32).reshape(2, 24)
    pool = pool.append(0, new, jnp.asarray([2, 0]), jnp.asarray([5, 1]))
    np.testing.assert_array_equal(np.asarray(pool.latent[0, 2, 5]),
                                  np.asarray(new[0, :16]))
    np.testing.assert_array_equal(np.asarray(pool.rope[0, 2, :, 5]),
                                  np.asarray(new[0, 16:]))
    clean = pool.scrub_null_block()
    assert not np.asarray(clean.latent[:, 0], np.float32).any()
    assert not np.asarray(clean.rope[:, 0], np.float32).any()
    with pytest.raises(ValueError, match="multiple of block_size"):
        pool.write_layer_blocks(0, rows[:12], jnp.asarray([1, 2]))


def test_the_engine_counts_the_latent_reads_and_the_stretched_positions(
        served):
    c = served["counters"]
    # a served slot reads its cursor's rows and its own, every step
    assert c["serve/latent_positions_read"] == sum(served["reads"]) > 0
    assert served["engine"].latent and served["engine"].rope_original == 16
    assert c["serve/positions_past_rope_original"] == sum(
        max(0, n - 16) for n in served["lengths"])
    tokens = sum(len(s) - 1 for s in served["streams"])
    # one read a decode token at least (its own row), at most max_len
    assert tokens < c["serve/latent_positions_read"] < 96 * tokens
    # the expert layer counts as Cohere's and Nemotron's do
    assert sum(c[f"serve/expert_assignments/{e}"] for e in range(4)) > 0


def test_the_decode_step_writes_the_latent_pool_where_it_lies():
    """``jit_decode_step`` over a donated cache aliases the pool to its
    result: the rows are appended in place, not copied."""
    model = program()
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    _, cache = _fresh(model)
    S = 3
    args = (params, cache, jnp.zeros((S, 1), jnp.int32),
            {KIND: jnp.zeros((S, 12), jnp.int32)},
            jnp.zeros((S,), jnp.int32), {KIND: jnp.zeros((S,), jnp.int32)},
            jnp.zeros((S,), jnp.int32))
    fn = lambda w, c, t, tab, ln, ids, off: model.forward(
        w, t, kv_cache=c, block_tables=tab, lengths=ln,
        append_block_ids=ids, append_offsets=off)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    assert compiled.memory_analysis().alias_size_in_bytes >= cache.nbytes()


def _share(lp, chip, per=2):
    held = slice(per * chip, per * chip + per)
    return dict(lp, w_gate=lp["w_gate"][held], w_up=lp["w_up"][held],
                w_down=lp["w_down"][held])


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The parts eight chips' PROGRAMS give for one layer (2 of 16 experts
    each, attention whole on every chip), with what every chip computes
    alike (attention, the shared expert, the residual) counted once, are
    the uncut REFERENCE's layer: the router keeps its width and its top-4,
    a token's weights are normalised over all four picks, and only held
    picks add."""
    uncut = dict(CFG, n_routed_experts=16, num_hidden_layers=1,
                 router_bias_std=0.3)
    w = weights(uncut)
    lp = {n: v[0] for n, v in w["layers"][KIND].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    want = reference.layer(uncut, lp, x, False)
    eps = CFG["rms_norm_eps"]
    mid = x + reference.attention(uncut, lp, reference.norm(
        x, lp["norm"], eps), False)
    shared = reference.expert_parts(uncut, lp, reference.norm(
        mid, lp["ff_norm"], eps), False)[1]
    total = x
    for chip in range(8):
        cut = dict(CFG, n_routed_experts=2, first_held_expert=2 * chip,
                   num_hidden_layers=1)
        model = program(cut)
        share = _share(lp, chip)
        big = {n: share.pop(n)[None] for n in model.experts.stacked}
        part, _, _ = model._prefill_layer(KIND, share, big, 0, x, None, None,
                                          None)
        total = total + (part - x)
    # attention and the shared expert came from every chip: once is theirs.
    # Eight programs' float32 sums (and the flash softmax) against the one
    # reference: 2e-4 of outputs of order 1
    total = total - 7 * (mid - x) - 7 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_prefix_sharing_and_speculation_go_on_refusing_a_by_kind_model():
    model, w = program(), weights()
    kw = dict(max_seqs=2, max_len=32, prefill_len=[16], block_size=BLOCK,
              cache_dtype=jnp.float32, num_blocks={KIND: 9})
    for bad in (dict(speculate_k=2), dict(prefix_suffix_cap=4)):
        with pytest.raises(ValueError):
            ServingEngine(model, w, **kw, **bad)

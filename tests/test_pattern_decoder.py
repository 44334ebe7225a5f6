"""The pattern decoder (window + full layers, grouped KV heads, parallel
block, a chip's share of dropless top-k experts) against the plain
reference of ``benchmark/families/cohere2_moe_reference.py``, at a size the
CPU holds: hidden 64, 8 query / 2 KV heads of 8, 16 experts top-2 of which
4 are held beside 2 shared, window 16, 4 layers, block 8. The weights are
the reference's (bfloat16-stored); the program computes in float32 here so
that agreement is tight."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.pattern_decoder import (PatternDecoder,
                                             PatternDecoderConfig,
                                             rotary_interleaved)
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.serving import (PagedServingEngine, Request, ServingEngine,
                              SlotScheduler)
from apex_tpu.serving.cache import (NULL_BLOCK, BlockAllocator,
                                    KindBlockAllocator)
from apex_tpu.transformer.expert_parallel import HeldExpertsMLP
from benchmark.families import cohere2_moe_reference as reference

fa = importlib.import_module("apex_tpu.ops.flash_attention")

CFG = {
    "vocab_size": 128, "hidden_size": 64, "intermediate_size": 32,
    "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
    "router_width": 16, "held_experts": [1, 4, 9, 14],
    "num_experts_per_tok": 2, "num_shared_experts": 2,
    "sliding_window": 16, "rope_theta": 50000, "layer_norm_eps": 1e-5,
    "logit_scale": 1, "max_position_embeddings": 128,
    "initializer_range": 0.3,
}
BLOCK = 8


def program_config(cfg=CFG, **over):
    kw = dict(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=reference.layer_types(cfg),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], expert_size=cfg["intermediate_size"],
        num_experts=cfg["router_width"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        held_experts=tuple(cfg["held_experts"]),
        num_shared_experts=cfg["num_shared_experts"],
        sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]),
        max_position_embeddings=cfg["max_position_embeddings"],
        compute_dtype=jnp.float32)
    kw.update(over)
    return PatternDecoderConfig(**kw)


def weights(cfg=CFG, seed=5):
    lo, hi = reference.seed_key(seed)
    return jax.jit(lambda lo, hi: reference.make_weights(cfg, lo, hi))(lo, hi)


@pytest.fixture(scope="module")
def served():
    """Six prompts (two past the window, three past the first prefill
    bucket) served through the scheduler over the paged engine."""
    model = PatternDecoder(program_config())
    w = weights()
    engine = PagedServingEngine(
        model, w, max_seqs=4, max_len=96, prefill_len=[16, 32, 64],
        block_size=BLOCK, cache_dtype=jnp.float32,
        num_blocks={"sliding_attention": 4 * 4 + 1,
                    "full_attention": 4 * 12 + 1})
    registry = MetricsRegistry()
    sched = SlotScheduler(engine, registry=registry)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 128, n).tolist() for n in (5, 20, 40, 64,
                                                          33, 9)]
    for i, p in enumerate(prompts):
        sched.submit(Request(prompt=p, max_new_tokens=24, temperature=0.0,
                             request_id=i))
    most = {}
    while sched.pending:
        sched.step()
        for kind, n in engine.allocator.blocks_in_use.items():
            most[kind] = max(most.get(kind, 0), n)
    done = {c.request_id: c for c in sched.drain_completed()}
    return dict(model=model, w=w, engine=engine, prompts=prompts,
                streams=[list(done[i].tokens) for i in range(len(prompts))],
                counters=registry.snapshot(), most=most)


def test_served_tokens_are_the_references_argmax(served):
    ref = reference.ServeReference(CFG, 96)
    gaps, _ = ref.gaps(served["w"], served["prompts"], served["streams"])
    assert all(len(g) == 24 for g in gaps)
    assert max(float(g.max()) for g in gaps) < 1e-4


def test_the_engine_holds_the_weights_it_was_handed(served):
    """Every leaf is stored in the dtype of its use and the model names no
    serving image of its own: the programs take the handed arrays, and
    the two weight gauges read the same."""
    engine = served["engine"]
    held = jax.tree_util.tree_leaves(engine.params)
    handed = jax.tree_util.tree_leaves(served["w"])
    assert len(held) == len(handed)
    assert all(a is b for a, b in zip(held, handed))
    assert engine._image_compiled is None
    c = served["counters"]
    assert c["serve/weights_held_bytes"] == c["serve/weights_handed_bytes"] \
        == sum(leaf.nbytes for leaf in handed)


def test_full_forward_logits_agree_with_the_reference(served):
    tokens = jnp.asarray(served["prompts"][3] + served["streams"][3])
    got = jax.jit(served["model"].__call__)(served["w"], tokens)
    want = jax.jit(lambda w, t: reference.forward(CFG, w, t))(
        served["w"], tokens)
    assert got.shape == want.shape == (88, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-4)


def test_paged_decode_logits_agree_with_the_reference(served):
    """Prefill 40 tokens (two buckets up, past the window), then decode 12
    more against the pools: every step's logits are the reference's row."""
    from apex_tpu.serving.cache import KindPagedKVCache
    model, w = served["model"], served["w"]
    cfg = model.cfg
    seq = served["prompts"][2] + served["streams"][2][:12]
    P = 40
    alloc = KindBlockAllocator(cfg.cache_kinds, {"sliding_attention": 9,
                                                 "full_attention": 13},
                               BLOCK, 12, 2)
    cache = KindPagedKVCache.create(
        cfg.cache_kinds, {"sliding_attention": 9, "full_attention": 13},
        2, BLOCK, 8, dtype=jnp.float32)
    plan = alloc.admit(1, seq[:P], 64 // BLOCK)
    padded = np.zeros((1, 64), np.int32)
    padded[0, :P] = seq[:P]
    row = {k: np.asarray(r, np.int32) for k, r in plan.block_row.items()}
    logits, cache, _ = jax.jit(
        lambda w, c, t, r: model.forward(w, t, kv_cache=c, block_row=r,
                                         prompt_len=P))(w, cache, padded,
                                                        row)
    want = np.asarray(jax.jit(lambda w, t: reference.forward(CFG, w, t))(
        w, jnp.asarray(seq)))
    np.testing.assert_allclose(np.asarray(logits)[0, :P], want[:P],
                               atol=2e-4, rtol=1e-4)
    step = jax.jit(lambda w, c, t, tab, ln, ids, off: model.forward(
        w, t, kv_cache=c, block_tables=tab, lengths=ln,
        append_block_ids=ids, append_offsets=off))
    active = np.array([False, True])
    for pos in range(P, len(seq)):
        assert alloc.prepare_step([1]).failed == []
        ids, off = alloc.append_targets(active)
        tok = np.array([[0], [seq[pos]]], np.int32)
        logits, cache, stats = step(
            w, cache, tok, {k: t.copy() for k, t in alloc.tables.items()},
            alloc.lengths.copy(), ids, off)
        alloc.advance([1])
        np.testing.assert_allclose(np.asarray(logits)[1], want[pos],
                                   atol=2e-4, rtol=1e-4)
        # the idle slot is routed nowhere: one token's picks a layer
        assert int(np.asarray(stats)[:, :-1].sum()) <= 4 * 2


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_a_planted_fault_in_the_programs_place_is_refused(served, fault):
    """The token each fault puts first lies far below the reference's best:
    served in the program's place it would fail the tiny cell's limit."""
    ref = reference.ServeReference(CFG, 96, control=fault)
    _, low = ref.gaps(served["w"], served["prompts"], served["streams"])
    assert float(np.concatenate(low).mean()) > 0.01, fault


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The attention and routed parts of eight shares (two query heads on
    one KV head and two experts each), with the shared experts and the
    residual counted once, are the uncut reference's layer."""
    uncut = dict(CFG, num_hidden_layers=1, num_attention_heads=16,
                 num_key_value_heads=8, held_experts=list(range(16)))
    w = weights(uncut)
    lp = {n: v[0] for n, v in w["layers"]["sliding_attention"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (24, 64), jnp.float32)
    want = reference.layer(uncut, lp, x, "sliding_attention", False)
    total = x
    for chip in range(8):
        heads = slice(2 * chip * 8, (2 * chip + 2) * 8)
        kv = slice(chip * 8, (chip + 1) * 8)
        experts = [2 * chip, 2 * chip + 1] + ([16, 17] if chip == 0 else [])
        model = PatternDecoder(program_config(
            uncut, num_attention_heads=2, num_key_value_heads=1,
            held_experts=(2 * chip, 2 * chip + 1),
            num_shared_experts=2 if chip == 0 else 0))
        share = {"norm": lp["norm"], "wq": lp["wq"][:, heads],
                 "wk": lp["wk"][:, kv], "wv": lp["wv"][:, kv],
                 "wo": lp["wo"][heads], "router": lp["router"]}
        big = {n: lp[n][jnp.asarray(experts)][None]
               for n in ("w_gate", "w_up", "w_down")}
        part, _, _ = model._prefill_layer("sliding_attention", share, big,
                                          0, x, None, None, None)
        total = total + (part - x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=5e-5, rtol=1e-4)


# -- the expert layer -----------------------------------------------------------

def expert_layer(**kw):
    return HeldExpertsMLP(64, 32, 16, 2, held=(3, 7, 8, 12), num_shared=2,
                          params_dtype=jnp.float32, **kw)


def test_the_sorted_product_is_the_dense_one_and_counts_what_it_routed():
    sorted_, dense = expert_layer(), expert_layer(use_pallas=False)
    p = sorted_.init(jax.random.PRNGKey(0))
    p["router"] = p["router"] * 50
    x = jax.random.normal(jax.random.PRNGKey(1), (40, 64))
    valid = jnp.arange(40) < 33
    got, stats = sorted_(p, x, valid)
    want, _ = dense(p, x, valid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    local, _ = sorted_.route(p["router"], x, valid)
    routed = np.asarray(local)[:, :2]
    assert list(np.asarray(stats["load"])) == [
        int((routed == e).sum()) for e in range(4)]
    assert int(stats["no_held_pick"]) == int(
        ((routed < 0).all(axis=1) & np.asarray(valid)).sum())
    # a padded row is routed nowhere and adds nothing
    assert float(jnp.abs(got[33:]).max()) == 0.0


def test_the_product_reads_the_layer_it_is_told_of_a_stacked_array():
    layer = expert_layer()
    p = layer.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
    want, _ = layer(p, x)
    stacked = {k: (jnp.stack([v * 0, v]) if k != "router" else v)
               for k, v in p.items()}
    got, _ = jax.jit(lambda p, x, i: layer(p, x, layer=i))(stacked, x, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


def test_under_a_mesh_axis_the_chips_parts_are_summed():
    """Eight chips, two experts each, the layer's one exchange (psum): the
    sum is one chip holding all sixteen."""
    from jax.sharding import Mesh, PartitionSpec as P
    whole = HeldExpertsMLP(64, 32, 16, 2, held=tuple(range(16)),
                           num_shared=2, params_dtype=jnp.float32)
    p = whole.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (24, 64))
    want, _ = whole(p, x)
    mesh = Mesh(np.array(jax.devices()[:8]), ("ep",))

    def chip(router, x, routed, shared):
        part = HeldExpertsMLP(64, 32, 16, 2, held=(0, 1), num_shared=2,
                              axis_name="ep", params_dtype=jnp.float32)
        params = {"router": router}
        for n in ("w_gate", "w_up", "w_down"):
            params[n] = jnp.concatenate([routed[n], shared[n]], axis=0)
        # chip i holds the experts of published ids 2i and 2i + 1
        return part(params, x, held=2 * jax.lax.axis_index("ep")
                    + jnp.arange(2))[0]

    routed = {n: p[n][:16] for n in ("w_gate", "w_up", "w_down")}
    shared = {n: p[n][16:] for n in ("w_gate", "w_up", "w_down")}
    got = jax.jit(jax.shard_map(
        chip, mesh=mesh, in_specs=(P(), P(), P("ep"), P()), out_specs=P(),
        check_vma=False))(p["router"], x, routed, shared)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


# -- kernels ----------------------------------------------------------------------

@pytest.mark.parametrize("seq,blocks", [
    (256, dict(block_q=64, block_k=128)),
    # 3 x 3 sub-tiles of 128 in a grid tile: the window's left edge, the
    # unmasked interior and the diagonal are three loops, and no window
    # below is a multiple of the sub-tile
    (768, dict(block_q=384, block_k=384)),
])
@pytest.mark.parametrize("window", [None, 40, 128, 300])
def test_flash_window_and_grouped_heads_match_the_oracle(window, seq, blocks):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (1, 8, seq, 8))
    k = jax.random.normal(keys[1], (1, 2, seq, 8))
    v = jax.random.normal(keys[2], (1, 2, seq, 8))
    got = fa.flash_attention(q, k, v, causal=True, window=window,
                             use_pallas=True, **blocks)
    want = fa.mha_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_flash_window_refuses_what_it_cannot_serve():
    q = jnp.zeros((1, 4, 128, 8))
    with pytest.raises(ValueError, match="causal"):
        fa.flash_attention(q, q, q, window=16)
    with pytest.raises(ValueError, match="forward-only"):
        fa.flash_attention(q, q[:, :2], q[:, :2], causal=True,
                           dropout_rate=0.1, dropout_seed=1)


@pytest.mark.parametrize("window", [None, 16, 20, 1])
def test_paged_decode_window_reads_no_block_outside_it(window):
    """The table entries left of the window are NULL here: a kernel that
    read them would read the null block's zeros and miss the oracle (which
    reads the true blocks and masks)."""
    h, hkv, d, S, bs, per_slot = 8, 2, 8, 3, 8, 8
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    kp = jax.random.normal(keys[0], (2, 1 + S * per_slot, bs, hkv * d))
    vp = jax.random.normal(keys[1], kp.shape)
    kp, vp = kp.at[:, NULL_BLOCK].set(1e3), vp.at[:, NULL_BLOCK].set(1e3)
    tables = np.arange(1, 1 + S * per_slot, dtype=np.int32).reshape(
        S, per_slot)
    lengths = np.array([5, 37, 63], np.int32)
    q = jax.random.normal(keys[2], (S, h, d))
    kn = jax.random.normal(keys[3], (S, hkv, d))
    vn = jax.random.normal(keys[4], (S, hkv, d))
    holed = tables.copy()
    if window is not None:
        for s in range(S):
            holed[s, :max(lengths[s] - window + 1, 0) // bs] = NULL_BLOCK
    got = fa.paged_decode_attention(q, kp, vp, 1, holed, lengths, k_new=kn,
                                    v_new=vn, window=window)
    want = fa.paged_decode_attention(q, kp, vp, 1, tables, lengths,
                                     k_new=kn, v_new=vn, window=window,
                                     use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


def test_rotary_pairs_are_interleaved():
    x = jnp.arange(8.0).reshape(1, 1, 8)
    out = np.asarray(rotary_interleaved(x, jnp.array([3]), 50000.0))[0, 0]
    ang = 3.0 * 50000.0 ** (-np.arange(0, 8, 2) / 8)
    np.testing.assert_allclose(out[0::2], np.arange(0, 8, 2) * np.cos(ang)
                               - np.arange(1, 8, 2) * np.sin(ang), rtol=1e-5)
    np.testing.assert_allclose(out[1::2], np.arange(0, 8, 2) * np.sin(ang)
                               + np.arange(1, 8, 2) * np.cos(ang), rtol=1e-5)


# -- pools by layer kind ------------------------------------------------------------

def test_window_blocks_come_back_and_none_that_is_readable_does():
    alloc = BlockAllocator(num_blocks=12, block_size=8, blocks_per_slot=12,
                           max_seqs=2, window=16)
    plan = alloc.admit(0, list(range(40)), 8)
    # the row at cursor 40 reads positions 25..39: blocks 3 and 4
    assert [b != NULL_BLOCK for b in plan.block_row] == \
        [False, False, False, True, True, False, False, False]
    assert alloc.blocks_in_use == 2
    for _ in range(30):
        assert alloc.prepare_step([0]).failed == []
        alloc.advance([0])
        cursor = int(alloc.lengths[0])
        first_read = max(0, cursor - 16 + 1)
        held = [i for i in range(12) if alloc.tables[0, i] != NULL_BLOCK]
        # every block a later row can read is still mapped ...
        assert held and held[0] <= first_read // 8
        assert held[-1] >= (cursor - 1) // 8
        # ... and at most window / block + 1 are
        assert len(held) <= 16 // 8 + 1
    assert alloc.blocks_returned == alloc.blocks_given - alloc.blocks_in_use
    assert alloc.blocks_returned > 0
    # the slot's next tenant starts at block 0 again: what the window
    # handed back for the last one is not taken as handed back for this
    alloc.release(0)
    alloc.admit(0, list(range(5)), 8)
    for _ in range(30):
        assert alloc.prepare_step([0]).failed == []
        alloc.advance([0])
        assert np.count_nonzero(alloc.tables[0] != NULL_BLOCK) <= 16 // 8 + 1
    assert alloc.tables[0, 0] == NULL_BLOCK


def test_both_tables_survive_release_and_readmission():
    kinds = {"sliding_attention": (3, 16), "full_attention": (1, None)}
    alloc = KindBlockAllocator(kinds, {"sliding_attention": 9,
                                       "full_attention": 25}, 8, 12, 2)
    free = alloc.free_blocks
    for _ in range(3):
        plan = alloc.admit(0, list(range(50)), 8)
        assert sum(b != NULL_BLOCK
                   for b in plan.block_row["full_attention"]) == 7
        assert sum(b != NULL_BLOCK
                   for b in plan.block_row["sliding_attention"]) == 3
        alloc.admit(1, list(range(9)), 2)
        for _ in range(20):
            assert alloc.prepare_step([0, 1]).failed == []
            ids, offsets = alloc.append_targets(np.array([True, True]))
            assert all((ids[k] != NULL_BLOCK).all() for k in ids)
            alloc.advance([0, 1])
        assert (alloc.lengths == [70, 29]).all()
        assert alloc.blocks_in_use["sliding_attention"] <= 2 * 3
        alloc.release(0)
        alloc.release(1)
        assert alloc.free_blocks == free
        assert all((t == NULL_BLOCK).all() for t in alloc.tables.values())
    # an admission the window pool cannot hold takes nothing anywhere
    small = KindBlockAllocator(kinds, {"sliding_attention": 4,
                                       "full_attention": 25}, 8, 12, 2)
    small.admit(0, list(range(30)), 4)
    with pytest.raises(Exception, match="exhausted"):
        small.admit(1, list(range(30)), 4)
    assert (small.tables["full_attention"][1] == NULL_BLOCK).all()
    assert small.kinds["full_attention"].blocks_in_use == 4


def test_the_engine_counts_by_kind_and_returns_window_blocks(served):
    counters, most = served["counters"], served["most"]
    assert counters["serve/window_blocks_returned"] > 0
    assert counters["serve/window_blocks_returned"] \
        <= counters["serve/window_blocks_given"]
    # the model names its counters, the scheduler only adds them up
    assert served["engine"].stats_names == tuple(
        f"expert_assignments/{e}" for e in range(4)) \
        + ("tokens_without_held_pick",)
    landed = sum(counters[f"serve/expert_assignments/{e}"]
                 for e in range(4))
    # padding is never counted: every real token is routed in four layers,
    # to two picks, and each either lands on a held expert or not
    tokens = sum(len(p) + len(s) - 1 for p, s in
                 zip(served["prompts"], served["streams"]))
    assert 0 < landed <= 4 * 2 * tokens
    assert counters["serve/tokens_without_held_pick"] <= 4 * tokens
    assert counters["serve/blocks_in_use/sliding_attention"] == 0
    # four slots at most, a window of 16 = at most 3 blocks of 8 a slot
    assert 0 < most["sliding_attention"] <= 4 * 3
    assert most["full_attention"] > most["sliding_attention"]


def test_what_refuses_this_model_says_why():
    model = PatternDecoder(program_config())
    w = weights()
    with pytest.raises(ValueError, match="dict by layer kind"):
        ServingEngine(model, w, max_seqs=2, max_len=64, prefill_len=32)
    common = dict(max_seqs=2, max_len=64, prefill_len=32, block_size=8,
                  num_blocks={"sliding_attention": 9, "full_attention": 17})
    with pytest.raises(ValueError, match="speculation"):
        PagedServingEngine(model, w, speculate_k=2, **common)
    with pytest.raises(ValueError, match="prefix index"):
        PagedServingEngine(model, w, prefix_suffix_cap=8, **common)
    with pytest.raises(ValueError, match="dict by layer kind"):
        PagedServingEngine(model, w, **dict(common, num_blocks=17))


# -- what the three served configurations computed before Jamba came ---------

@pytest.mark.parametrize("cell_name", ["tiny.chat", "tiny-cohere2-moe.rag",
                                       "tiny-nemotron-h.reason"])
def test_the_served_configurations_compute_what_they_did(cell_name):
    """The three configurations the benchmark served before PR 38 (gpt2,
    Cohere2 sparse, Nemotron-H), at their tiny cells' sizes and seed 11:
    the engine's parameter names and shapes are what they were, and the
    program's float32-compute logits over a fixed sequence are the
    recorded ones of commit 7862800 (``tests/data/serve_parent_logits.npz``,
    made there by the lines below), to float32 rounding: logits of order
    2-12, sums in another order on another CPU."""
    import dataclasses
    import os

    from benchmark import run as harness
    here = os.path.dirname(os.path.abspath(__file__))
    recorded = np.load(os.path.join(here, "data", "serve_parent_logits.npz"))
    cell, config = harness.load_cell(
        os.path.join(here, "benchmark", "cells"), cell_name)
    family = harness.load_family(config)
    engine = family.serve_engine(config, cell["engine"], 11)
    names = sorted(
        "/".join(str(getattr(k, "key", k)) for k in path) + " "
        + "x".join(map(str, leaf.shape)) for path, leaf in
        jax.tree_util.tree_flatten_with_path(engine.params_spec)[0])
    assert names == list(recorded[cell_name + ":names"])
    model = type(engine.model)(dataclasses.replace(
        engine.model.cfg, compute_dtype=jnp.float32))
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        1, family.vocab(config), 24))
    logits = model(engine.params, tokens[None])[0] \
        if config["family"] == "gpt2" else model(engine.params, tokens)
    np.testing.assert_allclose(np.asarray(logits, np.float32)[-4:],
                               recorded[cell_name + ":logits"],
                               atol=2e-4, rtol=1e-5)

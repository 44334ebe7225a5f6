"""The pattern decoder as Ouro, a looped stack (sandwich-normed layers of
rotary attention, pairs ``(j, j + d/2)``, then a dense gated MLP, the whole
stack run four times over its own output, the final norm after every pass)
against the plain reference of ``benchmark/families/ouro_reference.py``, at
a size the CPU holds: hidden 64, 4 heads of 16 (as many KV heads), 3
layers, 4 passes, an MLP of 96, a vocabulary of 97, block 8. The weights are
the reference's (bfloat16-stored); the program computes in float32 here so
that agreement is tight: what is left is the order of float32 sums (the
flash and paged softmaxes against the plain one, over 12 layers a token),
2e-6 of logits of order 3, held to 1e-4. The fp8 control and each planted
fault move the logits by 0.2 or more, three orders over the tolerance."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.pattern_decoder import (LAYER_KINDS, PatternDecoder,
                                             rotary_half)
from apex_tpu.serving import Request, ServingEngine, SlotScheduler
from apex_tpu.serving.cache import KindBlockAllocator, KindPagedKVCache
from benchmark.families import ouro as family

reference = family.reference
KIND = reference.KIND
HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "benchmark", "cells", "configs",
                       "tiny-ouro.json")) as _f:
    CFG = json.load(_f)
BLOCK, SLOTS, PER_SLOT = 8, 3, 12
TOL = dict(atol=1e-4, rtol=1e-4)


def program(cfg=CFG, **over):
    model = family.model(cfg)
    return PatternDecoder(dataclasses.replace(
        model.cfg, compute_dtype=jnp.float32, **over))


@functools.lru_cache(maxsize=None)
def weights(seed=5):
    lo, hi = reference.seed_key(seed)
    return jax.jit(lambda lo, hi: reference.make_weights(CFG, lo, hi))(lo, hi)


@functools.lru_cache(maxsize=None)
def _reference(mode, passes):
    cfg = dict(CFG, total_ut_steps=passes)
    return jax.jit(lambda w, t: reference.forward(cfg, w, t, mode))


def ref_logits(w, tokens, mode=False, passes=4):
    """``reference.forward``'s logits, compiled once a mode."""
    return np.asarray(_reference(mode, passes)(w, tokens))


@pytest.fixture(scope="module")
def served():
    """Five prompts through the scheduler over the paged engine on THREE
    slots: admitted at different steps, every slot given anew after a
    release."""
    model, w = program(), weights()
    engine = ServingEngine(
        model, w, max_seqs=SLOTS, max_len=96, prefill_len=[16, 32],
        block_size=BLOCK, cache_dtype=jnp.float32,
        num_blocks={KIND: SLOTS * PER_SLOT + 1})
    sched = SlotScheduler(engine)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 97, n).tolist() for n in (5, 20, 13, 32, 3)]
    for i, p in enumerate(prompts):
        sched.submit(Request(prompt=p, max_new_tokens=8 + 3 * i,
                             temperature=0.0, request_id=i))
    while sched.pending:
        sched.step()
    done = {c.request_id: c for c in sched.drain_completed()}
    return dict(model=model, w=w, engine=engine, prompts=prompts,
                streams=[list(done[i].tokens) for i in range(len(prompts))])


def test_the_layer_is_sandwiched_rotary_attention_then_a_dense_mlp():
    model = program()
    cfg = model.cfg
    assert cfg.layer_types == (KIND,) * 3 and cfg.loops == 4
    assert cfg.block == "sandwich" and not cfg.tie_embeddings
    assert LAYER_KINDS[KIND].rotary == "half" \
        and LAYER_KINDS[KIND].feed_forward == "dense"
    # the pool keeps a layer of K/V a pass: 4 x 3 cached layers over the
    # weights of 3
    assert cfg.cache_kinds == {KIND: (12, None)}
    shapes = model.param_shapes()
    assert shapes["layers"][KIND]["wq"] == (3, 64, 64)
    assert {"norm", "mixer_out_norm", "ff_norm", "ff_out_norm"} \
        <= set(shapes["layers"][KIND])
    assert jax.tree_util.tree_map(lambda x: x.shape, weights()) \
        == jax.tree_util.tree_map(lambda s: s, shapes,
                                  is_leaf=lambda s: isinstance(s, tuple))


def test_served_tokens_are_the_references_argmax(served):
    ref = reference.ServeReference(CFG, 96)
    gaps, _ = ref.gaps(served["w"], served["prompts"], served["streams"])
    assert [len(g) for g in gaps] == [8 + 3 * i for i in range(5)]
    assert max(float(g.max()) for g in gaps) < 1e-4


def test_full_forward_logits_agree_with_the_reference(served):
    tokens = jnp.asarray(served["prompts"][3] + served["streams"][3])
    got = jax.jit(served["model"].__call__)(served["w"], tokens)
    np.testing.assert_allclose(np.asarray(got),
                               ref_logits(served["w"], tokens), **TOL)


@pytest.mark.parametrize("mode", ("fp8",) + reference.FAULTS)
def test_the_control_and_each_planted_fault_break_the_tolerance(served,
                                                                mode):
    """The reference in fp8, and with each fault planted (a pass left out,
    every pass reading the first pass's K/V, no norm between passes,
    interleaved rotary pairs, the two output norms left out), is NOT
    within the tolerance the program holds."""
    tokens = jnp.asarray(served["prompts"][3] + served["streams"][3])
    want = ref_logits(served["w"], tokens)
    off = ref_logits(served["w"], tokens, mode)
    assert float(np.abs(off - want).max()) > 100 * TOL["atol"]


def _fresh(model):
    cfg = model.cfg
    blocks = {KIND: SLOTS * PER_SLOT + 1}
    alloc = KindBlockAllocator(cfg.cache_kinds, blocks, BLOCK, PER_SLOT,
                               SLOTS)
    cache = KindPagedKVCache.create(cfg.cache_kinds, blocks, 4, BLOCK, 16,
                                    dtype=jnp.float32, max_seqs=SLOTS)
    return alloc, cache


def _prefill(model, w, alloc, cache, prompt, slot, bucket):
    plan = alloc.admit(slot, prompt, bucket // BLOCK)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    row = {k: np.asarray(r, np.int32) for k, r in plan.block_row.items()}
    return jax.jit(lambda w, c, t, r: model.forward(
        w, t, kv_cache=c, block_row=r, prompt_len=len(prompt)))(
            w, cache, padded, row)


def test_paged_prefill_then_decode_agree_with_the_full_forward(served):
    """Prefill 13 tokens in a bucket of 16 into slot 1 (slot 0 holds
    another request), then decode 14 more against the pool, every pass
    reading and appending its own layers: every step's logits are the
    reference's full-forward row at that position."""
    model, w = served["model"], served["w"]
    seq = served["prompts"][2] + served["streams"][2][:14]
    P = 13
    alloc, cache = _fresh(model)
    _, cache, _ = _prefill(model, w, alloc, cache, served["prompts"][1], 0,
                           32)
    logits, cache, _ = _prefill(model, w, alloc, cache, seq[:P], 1, 16)
    want = ref_logits(w, jnp.asarray(seq))
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


def test_the_pool_holds_a_layer_a_pass_and_the_engine_counts_them(served):
    """Pass 2's K/V of a layer differ from pass 1's at the same positions
    (the pass reads the normed output of the one before), and sit in cache
    layer 3 + l; the engine's pool is 4 x 3 layers deep, and what it holds
    is counted by those layers, not the weights' 3."""
    model, w = served["model"], served["w"]
    alloc, cache = _fresh(model)
    prompt = served["prompts"][1]
    _, cache, _ = _prefill(model, w, alloc, cache, prompt, 0, 32)
    pool = cache.pools[KIND]
    blocks = alloc.tables[KIND][0, :-(-len(prompt) // BLOCK)]
    k = np.asarray(pool.k[:, blocks]).reshape(12, -1, 64)[:, :len(prompt)]
    for layer in range(3):
        for other in range(1, 4):
            assert np.abs(k[other * 3 + layer] - k[layer]).max() > 0.1
    engine = served["engine"]
    assert engine.cache.pools[KIND].k.shape == (12, SLOTS * PER_SLOT + 1,
                                                BLOCK, 64)
    # K and V, 12 cached layers, 64 values a row, float32: per position
    assert engine.cache.nbytes() == 2 * 12 * 64 * 4 * BLOCK \
        * (SLOTS * PER_SLOT + 1)


def _scans(jaxpr):
    """The deepest nesting of ``scan`` in a jaxpr."""
    deepest = 0
    for eqn in jaxpr.eqns:
        inner = [_scans(p.jaxpr if hasattr(p, "jaxpr") else p)
                 for p in eqn.params.values()
                 if hasattr(p, "eqns") or hasattr(p, "jaxpr")]
        here = max(inner, default=0) + (eqn.primitive.name == "scan")
        deepest = max(deepest, here)
    return deepest


def test_one_loop_is_the_plain_sandwich_stack_and_builds_no_pass_scan():
    """With ``total_ut_steps`` 1 the model is the sandwich stack run once:
    the reference's one-pass forward, the final norm then the head, and no
    scan over passes (the periods' and the runs' scans alone)."""
    model = program(dict(CFG, total_ut_steps=1))
    w = {k: v for k, v in weights().items() if not k.startswith("exit_gate")}
    tokens = jnp.arange(1, 20)
    assert model.cfg.loops == 1 and set(model.param_shapes()) == set(w)
    got = jax.jit(model.__call__)(w, tokens)
    np.testing.assert_allclose(np.asarray(got),
                               ref_logits(w, tokens, passes=1), **TOL)
    depth = lambda m, w: _scans(jax.make_jaxpr(m.__call__)(w, tokens).jaxpr)
    assert depth(model, w) == 2
    assert depth(program(), weights()) == 3


def test_rotate_half_is_the_hf_formula_written_out():
    """``q cos + rotate_half(q) sin`` with ``cos = cat(cos(p inv), cos(p
    inv))``, ``rotate_half([a, b]) = [-b, a]``, ``inv_j = theta^(-2j/d)``
    (``transformers``' ``apply_rotary_pos_emb``), in numpy."""
    rng = np.random.default_rng(1)
    x = rng.standard_normal((5, 2, 8)).astype(np.float32)
    pos = np.array([0, 1, 7, 100, 3000])
    theta = 1e6
    inv = 1.0 / theta ** (np.arange(0, 8, 2) / 8.0)
    freqs = pos[:, None] * inv[None, :]
    emb = np.concatenate([freqs, freqs], axis=-1)[:, None, :]
    half = np.concatenate([-x[..., 4:], x[..., :4]], axis=-1)
    want = x * np.cos(emb) + half * np.sin(emb)
    got = rotary_half(jnp.asarray(x), jnp.asarray(pos), theta)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    # the reference's table is the same rotation at positions 0..T-1
    np.testing.assert_allclose(
        np.asarray(reference.rope(jnp.asarray(x), theta)),
        np.asarray(rotary_half(jnp.asarray(x), jnp.arange(5), theta)),
        atol=1e-5)


def test_per_token_depth_and_a_looped_state_are_refused_at_build():
    with pytest.raises(ValueError, match="early_exit_threshold"):
        family.model(dict(CFG, early_exit_threshold=0.9))
    # a pass keeps its own KV blocks; a per-slot state a pass is not written
    with pytest.raises(ValueError, match="looped"):
        dataclasses.replace(program().cfg, layer_types=("mamba_mlp",),
                            mamba1_inner=8, mamba1_dt_rank=2)

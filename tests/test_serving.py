"""Serving fast path: KV-cached prefill/decode vs the one-shot forward,
AOT donation + zero-recompile contracts of an engine over its default
pool, and the continuous slot batcher (docs/SERVING.md). The decode
kernel and the pool's own tests are in ``tests/test_paged.py``."""

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.ops.flash_attention import mha_reference
from apex_tpu.serving import (BlockAllocator, PagedKVCache, Request,
                              ServingEngine, SlotScheduler,
                              cache_bytes_per_slot, paged_block_bytes,
                              sample_tokens)
from apex_tpu.observability.registry import MetricsRegistry


# ---------------------------------------------------------------------------
# the cache oracle, and the arithmetic the pool's size rests on
# ---------------------------------------------------------------------------

class TestCacheOracle:
    def test_kv_length_oracle_masks_garbage(self):
        """mha_reference's kv_length path must be insensitive to cache
        content past the cursor — the property that makes it a valid
        oracle for a preallocated cache."""
        rng = np.random.RandomState(4)
        q = jnp.asarray(rng.randn(2, 2, 1, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 2, 32, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 2, 32, 8), jnp.float32)
        lengths = jnp.asarray([5, 20])
        ref = mha_reference(q, k, v, kv_length=lengths)
        trash = mha_reference(
            q, k.at[0, :, 5:].set(1e4).at[1, :, 20:].set(-1e4),
            v.at[0, :, 5:].set(7.0), kv_length=lengths)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(trash))

    def test_bytes_per_slot(self):
        bf16 = cache_bytes_per_slot(12, 12, 1024, 64, jnp.bfloat16)
        assert bf16 == 2 * 12 * 12 * 64 * 2 * 1024
        i8 = cache_bytes_per_slot(12, 12, 1024, 64, jnp.int8)
        assert i8 == (2 * 12 * 12 * 64 + 2 * 12 * 12 * 4) * 1024
        # a slot's 1024 positions as 8 blocks of 128: the same bytes,
        # and a pool's bytes are its blocks'
        assert 8 * paged_block_bytes(12, 12, 128, 64, jnp.int8) == i8
        pool = jax.eval_shape(lambda: PagedKVCache.create(
            12, 3 * 8 + 1, 12, 128, 64, dtype=jnp.int8))
        assert pool.nbytes() == 3 * i8 + i8 // 8    # + the null block


# ---------------------------------------------------------------------------
# prefill + N decode steps vs the one-shot causal forward
# ---------------------------------------------------------------------------

def _tiny_model(compute_dtype):
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    compute_dtype=compute_dtype)
    model = GPTModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


class _Pool:
    """``model.forward``'s two cache legs driven as the engine drives
    them, without the engine: a pool that holds ``slots`` x ``max_len``
    and its allocator."""

    def __init__(self, model, slots, max_len=16, block=8,
                 dtype=jnp.float32):
        cfg = model.cfg
        per_slot = -(-max_len // block)
        blocks = slots * per_slot + 1
        self.model, self.block = model, block
        self.cache = PagedKVCache.create(
            cfg.num_layers, blocks, cfg.num_attention_heads, block,
            cfg.head_dim, dtype=dtype)
        self.alloc = BlockAllocator(blocks, block, per_slot, slots)

    def prefill(self, params, tokens, slot, prompt_len=None, **kw):
        P = tokens.shape[1]
        plan = self.alloc.admit(slot, [0] * (prompt_len or P),
                                P // self.block, share=False)
        logits, self.cache = self.model.forward(
            params, tokens, kv_cache=self.cache,
            block_row=np.asarray(plan.block_row, np.int32),
            prompt_len=prompt_len, **kw)
        return logits

    def decode(self, params, tokens, active):
        """One step: ``tokens`` ``(slots,)``, only ``active`` advance."""
        active = np.asarray(active, bool)
        step = self.alloc.prepare_step(list(np.flatnonzero(active)))
        assert not step.failed
        ids, offs = self.alloc.append_targets(active)
        logits, self.cache = self.model.forward(
            params, jnp.asarray(tokens)[:, None], kv_cache=self.cache,
            block_tables=self.alloc.tables.copy(),
            lengths=self.alloc.lengths.copy(), append_block_ids=ids,
            append_offsets=offs, cow_src=step.cow_src,
            cow_dst=step.cow_dst)
        self.alloc.advance(list(np.flatnonzero(active)))
        return logits


class TestPrefillDecodeParity:
    @pytest.mark.parametrize("compute,cache_dtype,tol", [
        # fp32 end to end: the decode path agrees with the one-shot
        # forward to fp32 roundoff (the reduction ORDER differs — block
        # streaming + two-way merge vs one softmax — so bitwise identity
        # is not the contract; docs/SERVING.md pins this tolerance)
        (jnp.float32, jnp.float32, 1e-5),
        # bf16 compute, bf16 cache: one bf16 rounding per cache write on
        # top of bf16 matmul noise
        (jnp.bfloat16, jnp.bfloat16, 0.05),
    ])
    def test_matches_oneshot_logits(self, compute, cache_dtype, tol):
        model, params = _tiny_model(compute)
        rng = np.random.RandomState(0)
        n, P, S = 12, 8, 3
        tokens = jnp.asarray(rng.randint(0, 97, (1, n)))
        oneshot = np.asarray(model(params, tokens), np.float32)

        pool = _Pool(model, S, dtype=cache_dtype)
        logits_p = pool.prefill(params, tokens[:, :P], slot=1)
        np.testing.assert_allclose(np.asarray(logits_p[0], np.float32),
                                   oneshot[0, :P], atol=tol)
        # teacher-forced decode of the remaining positions on slot 1 (the
        # other slots stay empty and step along — the fixed-shape grid)
        only = np.arange(S) == 1
        for t in range(P, n):
            dt = np.zeros(S, np.int32)
            dt[1] = int(tokens[0, t])
            logits_d = pool.decode(params, dt, only)
            np.testing.assert_allclose(np.asarray(logits_d[1], np.float32),
                                       oneshot[0, t], atol=tol)
        assert pool.alloc.lengths.tolist() == [0, n, 0]

    def test_int8_cache_stays_close(self):
        """int8 cache: quantization error bounded, ranking mostly
        preserved on the tiny model (argmax agreement is the serving
        quantity that matters)."""
        model, params = _tiny_model(jnp.float32)
        rng = np.random.RandomState(1)
        n, P = 10, 6
        tokens = jnp.asarray(rng.randint(0, 97, (1, n)))
        oneshot = np.asarray(model(params, tokens), np.float32)
        pool = _Pool(model, 1, block=2, dtype=jnp.int8)
        pool.prefill(params, tokens[:, :P], slot=0)
        agree = 0
        for t in range(P, n):
            logits_d = pool.decode(params, np.asarray(tokens[:, t]),
                                   [True])
            agree += int(np.argmax(np.asarray(logits_d[0]))
                         == np.argmax(oneshot[0, t]))
        assert agree >= (n - P) - 1

    def test_prompt_padding_is_invisible(self):
        """A right-padded prompt (prompt_len < window) must produce the
        same decode trajectory as an exact-width prefill: the cursor
        masks the pad garbage and the appends overwrite it."""
        model, params = _tiny_model(jnp.float32)
        toks = [5, 6, 7]

        def run(window):
            pool = _Pool(model, 1, block=1)
            padded = np.zeros((1, window), np.int32)
            padded[0, : len(toks)] = toks
            pool.prefill(params, jnp.asarray(padded), slot=0,
                         prompt_len=len(toks))
            return np.asarray(pool.decode(params, np.asarray([9]), [True]))

        np.testing.assert_allclose(run(3), run(8), atol=1e-5)

    def test_prompt_len_outside_window_guarded(self):
        """A cursor past the written window would make every later
        decode read stale cache: static prompt_len is rejected, a
        traced one (the AOT engine path) is clamped."""
        model, params = _tiny_model(jnp.float32)
        tokens = jnp.asarray([[1, 2, 3, 4]])
        pool = _Pool(model, 1, block=4)
        row = np.asarray([1], np.int32)
        with pytest.raises(ValueError, match="written window"):
            model.forward(params, tokens, kv_cache=pool.cache,
                          block_row=row, prompt_len=7)
        last = jax.jit(
            lambda p, c, pl: model.forward(
                p, tokens, kv_cache=c, block_row=row, prompt_len=pl,
                last_logit_only=True)[0])
        # clamped to the window: the row it projects is the last written
        np.testing.assert_array_equal(
            np.asarray(last(params, pool.cache, jnp.asarray(7, jnp.int32))),
            np.asarray(last(params, pool.cache, jnp.asarray(4, jnp.int32))))

    def test_forward_without_cache_is_call(self):
        model, params = _tiny_model(jnp.float32)
        tokens = jnp.asarray([[1, 2, 3]])
        np.testing.assert_array_equal(
            np.asarray(model.forward(params, tokens)),
            np.asarray(model(params, tokens)))

    def test_tp_refused(self):
        cfg = GPTConfig(vocab_size=32, hidden_size=16, num_layers=1,
                        num_attention_heads=2, max_position_embeddings=8,
                        tensor_model_parallel_size=2)
        model = GPTModel(cfg)
        with pytest.raises(NotImplementedError, match="tp=1"):
            model.forward({}, jnp.zeros((1, 4), jnp.int32),
                          kv_cache=PagedKVCache.create(1, 2, 2, 4, 8),
                          block_row=np.asarray([1], np.int32))


# ---------------------------------------------------------------------------
# AOT engine: donation, live buffers, zero recompiles
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    model, params = _tiny_model(jnp.float32)
    return ServingEngine(model, params, max_seqs=2, max_len=16,
                         prefill_len=8)


class TestEngineContracts:
    def test_cache_donation_aliased(self, engine):
        """Every cache leaf must be input/output-aliased in BOTH compiled
        programs: alias_bytes covers the whole cache, so decode steps do
        zero cache allocation (the PR 4 donation-test methodology)."""
        for compiled in (engine.decode_compiled, engine.prefill_compiled):
            assert "input_output_alias" in compiled.as_text()
            ma = compiled.memory_analysis()
            assert int(ma.alias_size_in_bytes) >= engine.cache.nbytes()

    def test_live_buffers_consumed(self, engine):
        """The donated cache buffers die at each call — the step updates
        in place instead of copying."""
        old = jax.tree_util.tree_leaves(engine.cache)
        engine.prefill([1, 2, 3], slot=0)
        assert all(leaf.is_deleted() for leaf in old)
        old = jax.tree_util.tree_leaves(engine.cache)
        engine.decode(np.zeros(2, np.int32), np.zeros(2, np.float32),
                      active=np.asarray([True, False]))
        assert all(leaf.is_deleted() for leaf in old)
        old = jax.tree_util.tree_leaves(engine.cache)
        engine.release_slot(0)
        assert all(leaf.is_deleted() for leaf in old)

    def test_zero_recompiles_across_steps(self, engine):
        """After one warm call of each program, admissions/decodes/
        retirements must never trace or compile again — the compile-storm
        counters (PR 1) stay flat."""
        from apex_tpu import observability as obs
        reg = MetricsRegistry()
        # warm every host path once (prefill, decode, release, rng
        # split, asarray)
        only = np.eye(2, dtype=bool)       # a held slot is not re-admitted
        engine.prefill([1, 2], slot=0)
        engine.decode(np.zeros(2, np.int32), np.zeros(2, np.float32),
                      active=only[0])
        engine.release_slot(0)
        obs.install_compile_listeners(reg)
        try:
            before = dict(reg.snapshot())
            for i in range(4):
                engine.prefill([1, 2, 3], slot=i % 2)
                engine.decode(np.asarray([i, i + 1], np.int32),
                              np.asarray([0.0, 0.7], np.float32),
                              active=only[i % 2])
                engine.release_slot(i % 2)
            after = reg.snapshot()
        finally:
            obs.uninstall_compile_listeners(reg)
        for name in ("jax/compiles", "jax/traces", "jax/lowerings"):
            assert after.get(name, 0.0) == before.get(name, 0.0), (
                name, before, after)

    def test_capacity_math(self, engine):
        per_slot = engine.bytes_per_slot()
        # the engine default cache dtype is bf16 regardless of compute
        assert per_slot == cache_bytes_per_slot(2, 4, 16, 8, jnp.bfloat16)
        overhead = engine.overhead_bytes()
        hbm = 1 << 30
        suggested = engine.suggest_max_seqs(hbm, reserve_fraction=0.1)
        if overhead is not None:
            assert suggested == (int(hbm * 0.9) - overhead) // per_slot
        assert engine.suggest_max_seqs(0) == 0  # no HBM, no slots
        # monotone in memory
        assert engine.suggest_max_seqs(2 * hbm) >= suggested

    def test_prompt_too_long_rejected(self, engine):
        with pytest.raises(ValueError, match="prefill window"):
            engine.prefill(list(range(9)), slot=0)

    def test_out_of_range_slot_rejected(self, engine):
        """An out-of-range slot would CLAMP inside the compiled
        dynamic_update_slice and silently clobber the last valid slot's
        in-flight sequence — it must bounce at the host boundary."""
        before = engine.allocator.lengths.copy()
        for slot in (engine.max_seqs, -1):
            with pytest.raises(ValueError, match="out of range"):
                engine.prefill([1, 2], slot=slot)
        np.testing.assert_array_equal(engine.allocator.lengths, before)

    def test_prefill_last_logit_only_matches_full_head(self):
        """The engine's single-row head projection equals the full-head
        logits at prompt_len - 1 (the head is per-position, so gathering
        the hidden row first changes nothing but the FLOPs)."""
        model, params = _tiny_model(jnp.float32)
        tokens = jnp.asarray([[3, 1, 4, 1, 5, 0, 0, 0]])

        def run(last_only):
            return np.asarray(_Pool(model, 1).prefill(
                params, tokens, slot=0, prompt_len=5,
                last_logit_only=last_only))

        full, last = run(False), run(True)
        assert last.shape == (1, 1, full.shape[-1])
        np.testing.assert_allclose(last[0, 0], full[0, 4], atol=1e-6)

    def test_rng_varies_sampling(self):
        """Two stochastic decodes of the same state draw different rngs
        (the engine splits its key per call)."""
        model, params = _tiny_model(jnp.float32)
        eng = ServingEngine(model, params, max_seqs=1, max_len=16,
                            prefill_len=4)
        k1 = eng._next_key()
        k2 = eng._next_key()
        assert not np.array_equal(np.asarray(k1), np.asarray(k2))


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

class TestSampling:
    def test_greedy_and_topk1(self):
        rng = jax.random.PRNGKey(0)
        logits = jnp.asarray(np.random.RandomState(0).randn(5, 33),
                             jnp.float32)
        greedy = sample_tokens(logits, rng, jnp.zeros(5))
        np.testing.assert_array_equal(np.asarray(greedy),
                                      np.argmax(np.asarray(logits), -1))
        topk1 = sample_tokens(logits, rng, jnp.full(5, 1.0), top_k=1)
        np.testing.assert_array_equal(np.asarray(topk1),
                                      np.asarray(greedy))

    def test_topk_restricts_support(self):
        logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]] * 64, jnp.float32)
        toks = sample_tokens(logits, jax.random.PRNGKey(1),
                             jnp.full(64, 5.0), top_k=2)
        assert set(np.asarray(toks).tolist()) <= {2, 3}

    def test_mixed_batch_greedy_rows_deterministic(self):
        logits = jnp.asarray(np.random.RandomState(1).randn(4, 16),
                             jnp.float32)
        temps = jnp.asarray([0.0, 1.0, 0.0, 1.0])
        a = sample_tokens(logits, jax.random.PRNGKey(2), temps)
        b = sample_tokens(logits, jax.random.PRNGKey(3), temps)
        np.testing.assert_array_equal(np.asarray(a)[[0, 2]],
                                      np.asarray(b)[[0, 2]])


# ---------------------------------------------------------------------------
# continuous slot batching
# ---------------------------------------------------------------------------

def _sched(max_seqs=2, max_len=32, prefill_len=8, **kw):
    model, params = _tiny_model(jnp.float32)
    eng = ServingEngine(model, params, max_seqs=max_seqs, max_len=max_len,
                        prefill_len=prefill_len, **kw)
    reg = MetricsRegistry()
    return SlotScheduler(eng, registry=reg), reg


class TestSlotScheduler:
    def test_all_requests_complete_with_exact_lengths(self):
        sched, reg = _sched()
        reqs = [Request(prompt=[1 + i, 2, 3], max_new_tokens=2 + i)
                for i in range(5)]
        out = sched.run(reqs)
        assert sorted(out) == list(range(5))
        for i, c in sorted(out.items()):
            assert c.finish_reason == "length"
            assert len(c.tokens) == 2 + i
            # completions carry the measured request-lifecycle latencies
            # (the full tracing/SLO surface: tests/test_reqtrace.py)
            assert c.queue_wait_ms >= 0.0
            assert c.ttft_ms >= c.queue_wait_ms
            assert c.e2e_ms >= c.ttft_ms and c.tpot_ms > 0.0
        snap = reg.snapshot()
        assert snap["serve/ttft_ms_count"] == 5.0
        assert snap["serve/e2e_ms_count"] == 5.0
        assert snap["serve/admitted"] == 5.0
        assert snap["serve/retired"] == 5.0
        assert snap["serve/prefill_tokens"] == 15.0
        assert snap["serve/generated_tokens"] == sum(2 + i
                                                     for i in range(5))
        assert snap["serve/active_slots"] == 0.0
        assert snap["serve/queue_depth"] == 0.0
        assert snap["serve/tokens_per_sec"] > 0.0

    def test_no_batch_barrier(self):
        """A short request retires mid-flight and its slot is re-admitted
        while the long request keeps decoding — the continuous-batching
        property itself."""
        sched, _ = _sched(max_seqs=2)
        long_id = sched.submit(Request(prompt=[1], max_new_tokens=12))
        short_id = sched.submit(Request(prompt=[2], max_new_tokens=3))
        late_id = sched.submit(Request(prompt=[3], max_new_tokens=2))
        # 2 slots: long+short admitted; late queued
        sched.step()
        assert sched.pending == 3 and len(sched.queue) == 1
        while not any(c.request_id == short_id for c in sched.completed):
            sched.step()
        done_at_short = {c.request_id for c in sched.completed}
        assert long_id not in done_at_short  # long is still mid-flight
        sched.run([])  # drain
        result = {c.request_id: c for c in sched.completed}
        assert len(result[late_id].tokens) == 2
        assert len(result[long_id].tokens) == 12
        # the late request was admitted into the freed slot and COMPLETED
        # before the long one finished — no barrier (with one, late could
        # only start after both retire)
        order = [c.request_id for c in sched.completed]
        assert order.index(late_id) < order.index(long_id)

    def test_eos_and_capacity_stops(self):
        sched, _ = _sched(max_seqs=1, max_len=6, prefill_len=4)
        # the tiny random model repeats a token; use it as eos
        probe = sched.run([Request(prompt=[1, 2], max_new_tokens=3)])
        eos = probe[0].tokens[-1]
        sched2, _ = _sched(max_seqs=1, max_len=6, prefill_len=4)
        out = sched2.run([
            Request(prompt=[1, 2], max_new_tokens=50, eos_token=eos),
            Request(prompt=[1, 2, 3], max_new_tokens=50),
        ])
        assert out[0].finish_reason == "eos"
        # 6-token cache, 3-token prompt: capacity retires it
        assert out[1].finish_reason == "capacity"
        assert len(out[1].tokens) == 3

    def test_single_token_request_completes_at_prefill(self):
        sched, reg = _sched(max_seqs=2)
        out = sched.run([Request(prompt=[4, 5], max_new_tokens=1)])
        assert len(out[0].tokens) == 1
        assert reg.snapshot().get("serve/decode_steps", 0.0) == 0.0

    def test_int8_engine_serves(self):
        sched, _ = _sched(cache_dtype=jnp.int8)
        out = sched.run([Request(prompt=[1, 2, 3], max_new_tokens=4)])
        assert len(out[0].tokens) == 4

    def test_submit_rejects_bad_prompts_loop_stays_alive(self):
        """Validation happens at submit, not mid-step: a bad request
        bounces off the caller and never kills the serving loop."""
        sched, _ = _sched()
        with pytest.raises(ValueError, match="prefill window"):
            sched.submit(Request(prompt=list(range(9))))
        with pytest.raises(ValueError, match="empty"):
            sched.submit(Request(prompt=[]))
        assert sched.pending == 0
        out = sched.run([Request(prompt=[1], max_new_tokens=2)])
        assert len(out[0].tokens) == 2

    def test_free_slots_never_grow_cursors(self):
        """Freed slots must not keep (or grow) cursors: the decode
        kernel's compute-skip prices a slot's math O(cursor), so a
        retired sequence left in place — or a free slot creeping one
        garbage position per step — would tax every later step. Retire
        resets (release_slot) and the decode active-mask freezes idle
        cursors."""
        sched, _ = _sched(max_seqs=2)
        sched.run([Request(prompt=[1, 2, 3], max_new_tokens=10)])
        # slot 0 ran 10 tokens then released; slot 1 idled 9 steps
        np.testing.assert_array_equal(sched.engine.allocator.lengths,
                                      [0, 0])
        assert sched.engine.allocator.blocks_in_use == 0

    def test_submit_rejects_nonpositive_max_new_tokens(self):
        sched, _ = _sched()
        with pytest.raises(ValueError, match="max_new_tokens"):
            sched.submit(Request(prompt=[1], max_new_tokens=0))
        assert sched.pending == 0

    def test_run_returns_only_this_runs_completions(self):
        sched, _ = _sched()
        first = sched.run([Request(prompt=[1], max_new_tokens=2)])
        second = sched.run([Request(prompt=[2], max_new_tokens=3,
                                    request_id=7)])
        assert sorted(first) == [0] and sorted(second) == [7]
        # the buffer holds both until drained; draining empties it
        assert {c.request_id for c in sched.completed} == {0, 7}
        assert len(sched.drain_completed()) == 2
        assert sched.completed == []

    def test_run_no_recompile_guard(self):
        """run(no_recompile=True) wraps the loop in the analysis
        engine's recompile_guard (PR 11): the steady-state serving loop
        is live-asserted recompile-free, not just test-asserted."""
        sched, _ = _sched()
        reqs = [Request(prompt=[1 + i, 2], max_new_tokens=3)
                for i in range(4)]
        out = sched.run(reqs, no_recompile=True)
        assert sorted(out) == list(range(4))
        # a second guarded run on the warm engine is also clean
        out = sched.run([Request(prompt=[9], max_new_tokens=2,
                                 request_id=9)], no_recompile=True)
        assert sorted(out) == [9]

"""Speculative decoding (docs/SERVING.md "Speculative decoding"): the
verify_tokens acceptance rule (greedy exact-prefix, rejection sampling
with the corrected residual), the self-drafting NGramDraftSource, the
k-token paged verify window at block boundaries (counts 0/1/k-1/k
across a block edge, pool-exhaustion mid-verify, saturation writing
nothing), the advance-by-accepted rollback invariant on an engine over
its default pool and over a smaller one, greedy spec-stream parity under the
zero-recompile guard, and mid-verify retirement (poison quarantine)
leaving no drafted-but-rejected KV visible to a re-admitted slot."""

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.elastic.faults import FaultPlan
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.serving import (BlockAllocator, DraftSource,
                              NGramDraftSource, PagedKVCache,
                              PagedServingEngine, Rejection, Request,
                              ServingEngine, SlotScheduler, verify_tokens)
from apex_tpu.serving.cache import NULL_BLOCK

K = 2  # the static draft window the spec engines below compile


# ---------------------------------------------------------------------------
# verify_tokens: the acceptance rule
# ---------------------------------------------------------------------------

class TestVerifyTokens:
    V = 7

    def _chain_logits(self, argmaxes):
        """(1, Q, V) logits whose per-row argmax is ``argmaxes``."""
        out = np.zeros((1, len(argmaxes), self.V), np.float32)
        for i, t in enumerate(argmaxes):
            out[0, i, t] = 5.0
        return jnp.asarray(out)

    @pytest.mark.parametrize("drafts,want_accepted,want_emit", [
        ([2, 4], 2, [2, 4, 1]),   # full accept + bonus
        ([2, 3], 1, [2, 4]),      # prefix accept, row 1 corrected
        ([3, 4], 0, [2]),         # first draft wrong: correction only
    ])
    def test_greedy_exact_prefix(self, drafts, want_accepted, want_emit):
        logits = self._chain_logits([2, 4, 1])
        toks, accepted = verify_tokens(
            logits, jnp.asarray([drafts], jnp.int32),
            jax.random.PRNGKey(0), jnp.zeros((1,), jnp.float32))
        assert int(accepted[0]) == want_accepted
        # the emitted window is the accepted prefix + one correction or
        # bonus — and on the greedy path every row IS the argmax, so the
        # stream is bitwise the non-speculative one
        emit = [int(t) for t in toks[0, : want_accepted + 1]]
        assert emit == want_emit

    def test_stochastic_sure_draft_always_accepts(self):
        # the draft carries ~all the model mass: rejection sampling
        # accepts it for every key
        logits = self._chain_logits([2, 4, 1]) * 20.0
        temps = jnp.ones((1,), jnp.float32)
        for seed in range(5):
            toks, accepted = verify_tokens(
                logits, jnp.asarray([[2, 4]], jnp.int32),
                jax.random.PRNGKey(seed), temps)
            assert int(accepted[0]) == 2
            assert [int(t) for t in toks[0, :2]] == [2, 4]

    def test_stochastic_rejection_never_emits_the_draft(self):
        # the draft has ~zero mass: always rejected, and the corrected
        # residual (draft mass zeroed) can never re-emit it
        logits = np.zeros((1, 2, self.V), np.float32)
        logits[0, :, 3] = -1e9
        logits = jnp.asarray(logits)
        for seed in range(8):
            toks, accepted = verify_tokens(
                logits, jnp.asarray([[3]], jnp.int32),
                jax.random.PRNGKey(seed), jnp.ones((1,), jnp.float32))
            assert int(accepted[0]) == 0
            assert int(toks[0, 0]) != 3

    def test_stochastic_marginal_is_exactly_the_model(self):
        """The rejection-sampling correctness property: accept-with-
        p(draft), resample-from-residual makes the emitted token's
        marginal EXACTLY softmax(logits/T) (docs/SERVING.md carries the
        two-line proof)."""
        V = 3
        logits = jnp.asarray([[[0.8, 0.1, -0.4],
                               [0.0, 0.0, 0.0]]], jnp.float32)
        temps = jnp.ones((1,), jnp.float32)
        drafts = jnp.asarray([[1]], jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(42), 600)
        toks = jax.vmap(
            lambda k: verify_tokens(logits, drafts, k, temps)[0])(keys)
        first = np.asarray(toks)[:, 0, 0]
        want = np.asarray(jax.nn.softmax(logits[0, 0]))
        got = np.bincount(first, minlength=V) / len(first)
        np.testing.assert_allclose(got, want, atol=0.07)

    def test_top_k_one_is_greedy_even_when_stochastic(self):
        logits = self._chain_logits([2, 4, 1])
        toks, accepted = verify_tokens(
            logits, jnp.asarray([[2, 4]], jnp.int32),
            jax.random.PRNGKey(0), jnp.ones((1,), jnp.float32), top_k=1)
        assert int(accepted[0]) == 2
        assert [int(t) for t in toks[0]] == [2, 4, 1]


# ---------------------------------------------------------------------------
# the self-drafting n-gram source
# ---------------------------------------------------------------------------

class TestNGramDraftSource:
    def test_periodic_context_proposes_the_continuation(self):
        src = NGramDraftSource()
        assert src.draft([1, 2, 3, 1, 2, 3, 1, 2], 3) == [3, 1, 2]

    def test_no_repeat_falls_back_to_last_token(self):
        src = NGramDraftSource()
        assert src.draft([5, 6, 7], 3) == [7, 7, 7]

    def test_short_continuation_pads_with_its_tail(self):
        src = NGramDraftSource()
        # suffix [1, 2] matches at the start; the continuation [1, 2]
        # runs out before k and pads with its last token
        assert src.draft([1, 2, 1, 2], 4) == [1, 2, 2, 2]

    def test_longest_suffix_match_wins(self):
        src = NGramDraftSource(max_ngram=3)
        # the 1-gram [9] also matches earlier, but the 2-gram [2, 9]
        # match is longer and pins the prediction to 7
        assert src.draft([2, 9, 7, 4, 9, 5, 2, 9], 1) == [7]

    def test_interface_is_abstract(self):
        with pytest.raises(NotImplementedError):
            DraftSource().draft([1, 2], 2)


# ---------------------------------------------------------------------------
# the k-token paged verify window: allocator + pool
# ---------------------------------------------------------------------------

def _alloc(num_blocks=10, block_size=4, blocks_per_slot=4, max_seqs=2):
    return BlockAllocator(num_blocks, block_size, blocks_per_slot,
                          max_seqs)


class TestPagedVerifyWindow:
    @pytest.mark.parametrize("count", [0, 1, 2, 3])  # 0, 1, k-1, k
    def test_window_across_block_edge_advances_by_count(self, count):
        """The PR 16 regression, extended: a 3-token verify window from
        cursor 3 crosses the block edge at 4 — each token names its own
        (block, offset), every row is physically written, and the
        cursor mirror moves by the ACCEPTED count only."""
        alloc = _alloc()
        alloc.admit(0, [11, 12, 13], prefill_blocks=1)
        plan = alloc.prepare_verify([0], 3)
        assert plan.failed == []
        b0, b1 = int(alloc.tables[0, 0]), int(alloc.tables[0, 1])
        assert b0 != NULL_BLOCK and b1 != NULL_BLOCK  # edge block mapped
        active = np.asarray([True, False])
        bids, offs = alloc.verify_targets(active, 3)
        np.testing.assert_array_equal(bids[0], [b0, b1, b1])
        np.testing.assert_array_equal(offs[0], [3, 0, 1])
        # the inactive slot's whole window aims at the null absorber
        assert np.all(bids[1] == NULL_BLOCK)

        pool = PagedKVCache.create(1, alloc.num_blocks, 1,
                                   alloc.block_size, 2, jnp.float32)
        val = np.zeros((1, 2, 1, 3, 2), np.float32)
        for s in range(2):
            for r in range(3):
                val[0, s, 0, r, :] = 100 * s + r + 1
        # one layer, one head: the token-major pool (L, NB, bs, H*D)
        # reads (L, NB, bs, D) here
        pool = pool.append_k(0, jnp.asarray(val[0]), jnp.asarray(val[0]),
                             jnp.asarray(bids), jnp.asarray(offs))
        k = np.asarray(pool.k)
        # write-all: every row of slot 0's window landed at its target,
        # accepted or not — rejected rows sit ABOVE the cursor, masked
        # from every read and overwritten by the next window
        for r, (b, o) in enumerate(zip(bids[0], offs[0])):
            np.testing.assert_array_equal(k[0, b, o], [r + 1, r + 1])
        # nothing outside the named blocks and the null absorber moved
        untouched = np.ones(alloc.num_blocks, bool)
        untouched[[NULL_BLOCK, b0, b1]] = False
        assert not np.any(k[0, untouched])

        alloc.advance_counts([0], [count])
        assert int(alloc.lengths[0]) == 3 + count
        # the next window starts exactly at the advanced cursor, so the
        # rejected tail (positions 3+count..5) is what it overwrites
        _, offs2 = alloc.verify_targets(active, 3)
        assert int(offs2[0, 0]) == (3 + count) % alloc.block_size

    def test_exhaustion_mid_verify_is_atomic_per_slot(self):
        alloc = _alloc(num_blocks=3, block_size=4, blocks_per_slot=4)
        alloc.admit(0, [1, 2, 3, 4], prefill_blocks=1)
        alloc.admit(1, [5, 6, 7, 8], prefill_blocks=1)
        assert alloc.free_blocks == 0
        # both slots' windows need a fresh edge block; the dry pool
        # fails them WITHOUT mutating tables or the free list
        plan = alloc.prepare_verify([0, 1], 3)
        assert plan.failed == [0, 1]
        assert alloc.free_blocks == 0
        assert int(alloc.tables[0, 1]) == NULL_BLOCK
        # and a failed slot's window aims at the null block end to end
        bids, _ = alloc.verify_targets(np.asarray([False, False]), 3)
        assert np.all(bids == NULL_BLOCK)

    def test_partial_grab_rolls_back(self):
        alloc = _alloc(num_blocks=4, block_size=4, blocks_per_slot=4)
        alloc.admit(0, [1, 2, 3, 4], prefill_blocks=1)
        alloc.admit(1, [5, 6, 7, 8], prefill_blocks=1)
        assert alloc.free_blocks == 1
        # a 6-token window from cursor 4 spans table entries 1 AND 2 —
        # two fresh blocks — but only one is free: the partial grab is
        # handed back (atomic per slot), not kept
        plan = alloc.prepare_verify([0], 6)
        assert plan.failed == [0]
        assert alloc.free_blocks == 1
        assert np.all(alloc.tables[0, 1:] == NULL_BLOCK)

    def test_saturation_masks_past_capacity_then_writes_nothing(self):
        alloc = _alloc(num_blocks=10, block_size=4, blocks_per_slot=2)
        alloc.admit(0, list(range(1, 8)), prefill_blocks=2)  # cursor 7/8
        assert alloc.prepare_verify([0], 3).failed == []
        bids, offs = alloc.verify_targets(np.asarray([True, False]), 3)
        # only position 7 fits; 8 and 9 sit past capacity -> null
        assert int(bids[0, 0]) == int(alloc.tables[0, 1])
        assert int(offs[0, 0]) == 3
        np.testing.assert_array_equal(bids[0, 1:], [NULL_BLOCK] * 2)
        alloc.advance_counts([0], [3])
        assert int(alloc.lengths[0]) == 8      # clamped at capacity
        # AT capacity: the slot fails preparation and the whole window
        # aims at the null block — a saturated slot writes nothing
        assert alloc.prepare_verify([0], 3).failed == [0]
        bids, _ = alloc.verify_targets(np.asarray([True, False]), 3)
        assert np.all(bids[0] == NULL_BLOCK)


# ---------------------------------------------------------------------------
# engines: advance-by-accepted + stream parity + retirement
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=64,
                    compute_dtype=jnp.float32)
    model = GPTModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def default_pool_ref(model_params):
    model, params = model_params
    return ServingEngine(model, params, max_seqs=2, max_len=24,
                         prefill_len=8, cache_dtype=jnp.float32)


@pytest.fixture(scope="module")
def default_pool_spec(model_params):
    model, params = model_params
    return ServingEngine(model, params, max_seqs=2, max_len=24,
                         prefill_len=8, cache_dtype=jnp.float32,
                         speculate_k=K, quarantine=True)


@pytest.fixture(scope="module")
def paged_ref(model_params):
    model, params = model_params
    return PagedServingEngine(model, params, max_seqs=2, max_len=24,
                              prefill_len=8, num_blocks=16, block_size=4,
                              cache_dtype=jnp.float32)


@pytest.fixture(scope="module")
def paged_spec(model_params):
    model, params = model_params
    return PagedServingEngine(model, params, max_seqs=2, max_len=24,
                              prefill_len=8, num_blocks=16, block_size=4,
                              cache_dtype=jnp.float32, speculate_k=K,
                              quarantine=True)


def _ref_stream(eng, prompt, n):
    """n-token greedy stream from the non-speculative engine."""
    out = [eng.prefill(prompt, 0)]
    toks = np.zeros(eng.max_seqs, np.int32)
    temps = np.zeros(eng.max_seqs, np.float32)
    active = np.asarray([True, False])
    for _ in range(n - 1):
        toks[0] = out[-1]
        out.append(int(eng.decode(toks, temps, active)[0]))
    eng.release_slot(0)
    return out


class TestEngineVerify:
    def test_validation(self, model_params):
        model, params = model_params
        with pytest.raises(ValueError, match="speculate_k"):
            ServingEngine(model, params, max_seqs=1, max_len=16,
                          prefill_len=4, speculate_k=-1)
        with pytest.raises(ValueError, match="verify window"):
            ServingEngine(model, params, max_seqs=1, max_len=8,
                          prefill_len=4, speculate_k=8)

    def test_verify_on_plain_engine_raises(self, default_pool_ref):
        assert default_pool_ref.verify_compiled is None
        with pytest.raises(ValueError, match="speculative"):
            default_pool_ref.verify(np.zeros(2, np.int32),
                             np.zeros((2, K), np.int32),
                             np.zeros(2, np.float32))

    def test_scheduler_engine_window_mismatch(self, default_pool_ref,
                                              default_pool_spec):
        with pytest.raises(ValueError, match="speculate_k"):
            SlotScheduler(default_pool_ref, registry=MetricsRegistry(),
                          speculate_k=K)
        with pytest.raises(ValueError, match="speculate_k"):
            SlotScheduler(default_pool_spec, registry=MetricsRegistry(),
                          speculate_k=K + 1)
        with pytest.raises(ValueError, match="draft_source"):
            SlotScheduler(default_pool_ref, registry=MetricsRegistry(),
                          draft_source=NGramDraftSource())
        # the default draft source rides in with speculate_k
        sched = SlotScheduler(default_pool_spec, registry=MetricsRegistry(),
                              speculate_k=K)
        assert isinstance(sched.draft_source, NGramDraftSource)

    @pytest.mark.parametrize("kind", ["default_pool", "paged"])
    def test_advance_by_accepted_and_rejected_kv_invisible(
            self, kind, request):
        """The satellite-4 invariant on both pool sizes: the cursor moves
        by exactly the accepted count, and a stream that suffered
        rejections stays bitwise the non-speculative greedy stream —
        rejected rows land above the cursor where no read masks them
        in, so there is nothing to roll back at ANY retirement point."""
        ref_eng = request.getfixturevalue(f"{kind}_ref")
        eng = request.getfixturevalue(f"{kind}_spec")
        prompt = [3, 1, 4, 1, 5]
        ref = _ref_stream(ref_eng, prompt, 12)

        assert eng.prefill(prompt, 0) == ref[0]
        got = [ref[0]]
        temps = np.zeros(eng.max_seqs, np.float32)
        active = np.asarray([True, False])
        for correct in [False, True, False, True]:
            i = len(got)
            draft_row = (ref[i:i + K] if correct
                         else [(ref[i] + 1) % 97] * K)
            toks = np.zeros(eng.max_seqs, np.int32)
            toks[0] = got[-1]
            drafts = np.zeros((eng.max_seqs, K), np.int32)
            drafts[0] = draft_row
            out, counts = eng.verify(toks, drafts, temps, active)
            c = int(counts[0])
            assert c == (K + 1 if correct else 1)
            assert int(counts[1]) == 0          # inactive slot frozen
            got.extend(int(t) for t in out[0, :c])
            cursor = eng.allocator.lengths
            # advance-by-accepted: prompt KV + every emitted-and-
            # consumed token, never the rejected tail
            assert int(cursor[0]) == len(prompt) + len(got) - 1
            assert int(cursor[1]) == 0
        assert got == ref[: len(got)]
        eng.release_slot(0)


class TestSchedulerSpeculative:
    PROMPTS = ([1, 2, 1, 2, 1, 2], [3, 4, 3, 4], [5, 5, 5, 5, 5])

    def _run(self, eng, speculate_k, **kw):
        reg = MetricsRegistry()
        sched = SlotScheduler(eng, registry=reg,
                              speculate_k=speculate_k, **kw)
        out = sched.run([Request(prompt=list(p), max_new_tokens=7)
                         for p in self.PROMPTS], no_recompile=True)
        return out, reg

    @pytest.mark.parametrize("kind", ["default_pool", "paged"])
    def test_greedy_stream_parity_zero_recompiles(self, kind, request):
        """The tentpole acceptance bar: greedy speculative streams are
        bitwise-identical to non-speculative greedy on both pool sizes,
        with the whole draft/verify/retire loop running under the live
        recompile guard (run(no_recompile=True))."""
        ref, _ = self._run(request.getfixturevalue(f"{kind}_ref"), 0)
        spec, reg = self._run(request.getfixturevalue(f"{kind}_spec"), K)
        assert sorted(spec) == sorted(ref)
        for rid in ref:
            assert spec[rid].tokens == ref[rid].tokens
            assert spec[rid].finish_reason == ref[rid].finish_reason
        snap = dict(reg.snapshot())
        # repetitive prompts: the n-gram source lands accepts, so the
        # verify steps amortize — fewer grid steps than tokens
        assert snap["serve/spec_steps"] >= 1.0
        assert snap["serve/spec_steps"] == snap["serve/decode_steps"]
        assert snap["serve/spec_drafted"] > 0
        assert 0.0 < snap["serve/spec_accept_rate"] <= 1.0
        assert snap["serve/spec_accepted"] > 0
        assert snap["serve/decode_steps"] < sum(
            7 - 1 for _ in self.PROMPTS)

    @pytest.mark.parametrize("kind", ["default_pool", "paged"])
    def test_poison_mid_verify_retires_clean(self, kind, request,
                                             tmp_path):
        """Satellite-4 negative test: a slot poisoned MID-VERIFY is
        quarantined before its window is harvested, the neighbor's
        stream is untouched, and a request re-admitted into the freed
        slot produces the clean-run stream — it can never read a
        drafted-but-rejected (or poisoned) KV entry."""
        eng = request.getfixturevalue(f"{kind}_spec")
        reqs = [Request(prompt=[7, 8, 7, 8], max_new_tokens=8),
                Request(prompt=[9, 1, 9, 1], max_new_tokens=8)]

        def run(plan):
            reg = MetricsRegistry()
            sched = SlotScheduler(eng, registry=reg, speculate_k=K,
                                  fault_plan=plan,
                                  dump_dir=str(tmp_path))
            out = sched.run([Request(prompt=list(r.prompt),
                                     max_new_tokens=r.max_new_tokens)
                             for r in reqs])
            return out, reg

        clean, _ = run(None)
        faulted, reg = run(FaultPlan(poison_logits={2: 0}))
        assert faulted[0].finish_reason == "poisoned"
        # everything delivered before the poisoned verify step is the
        # clean prefix; the poisoned window was discarded whole
        n = len(faulted[0].tokens)
        assert faulted[0].tokens == clean[0].tokens[:n]
        assert faulted[1].tokens == clean[1].tokens
        assert faulted[1].finish_reason == clean[1].finish_reason
        assert reg.snapshot()["serve/poisoned"] == 1.0
        # re-admission into the freed slots: the same work on the same
        # engine reproduces the clean streams exactly
        again, _ = run(None)
        for rid in clean:
            assert again[rid].tokens == clean[rid].tokens

    def test_paged_pool_exhaustion_speculative(self, model_params):
        """Submit-side: an impossible prompt gets the typed
        Rejection("pool_exhausted"). Mid-verify: a window the dry pool
        cannot map retires the slot loudly as "capacity" having
        emitted nothing that step."""
        model, params = model_params
        eng = PagedServingEngine(model, params, max_seqs=1, max_len=16,
                                 prefill_len=12, num_blocks=3,
                                 block_size=4, cache_dtype=jnp.float32,
                                 speculate_k=K)
        sched = SlotScheduler(eng, registry=MetricsRegistry(),
                              speculate_k=K)
        r = sched.submit(Request(prompt=list(range(1, 13)),  # 3 blocks
                                 max_new_tokens=12))
        assert isinstance(r, Rejection) and r.reason == "pool_exhausted"
        rid = sched.submit(Request(prompt=[1, 2, 3, 4],
                                   max_new_tokens=12))
        for _ in range(20):
            if not sched.pending:
                break
            sched.step()
        (comp,) = sched.completed
        assert comp.request_id == rid
        assert comp.finish_reason == "capacity"
        # grew from cursor 4 to the 8-token pool limit, then starved
        assert 1 <= len(comp.tokens) < 12

"""Serving resilience layer (docs/SERVING.md "Resilience"): admission
control + load shedding, deadlines + cancel, poison-slot quarantine,
graceful drain + zero-recompile hot weight swap, SLO brownout, and the
deterministic serving chaos plan — each contract proven, plus the
zero-cost-off assertion (three AOT programs byte-identical with every
feature off)."""

import json
import time

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.elastic.faults import FaultPlan
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.observability.reqtrace import RequestTrace
from apex_tpu.observability.slo import SLOTarget, SLOTracker
from apex_tpu.serving import (BrownoutPolicy, CheckpointWatcher,
                              Rejection, Request, ServingEngine,
                              SlotScheduler, watch_checkpoints)

from _program_text import program_text


@pytest.fixture(scope="module")
def model_params():
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_attention_heads=4, max_position_embeddings=64)
    model = GPTModel(cfg)
    return model, model.init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def engine(model_params):
    """Shared plain engine — tests must drain fully (and never swap its
    params) so slot/cache state is clean for the next one."""
    model, params = model_params
    return ServingEngine(model, params, max_seqs=2, max_len=32,
                         prefill_len=8)


@pytest.fixture(scope="module")
def qengine(model_params):
    """Shared quarantine engine (the poison check compiled in)."""
    model, params = model_params
    return ServingEngine(model, params, max_seqs=2, max_len=32,
                         prefill_len=8, quarantine=True)


def _sched(engine, **kw):
    reg = MetricsRegistry()
    return SlotScheduler(engine, registry=reg, **kw), reg


# ---------------------------------------------------------------------------
# admission control & load shedding
# ---------------------------------------------------------------------------

class TestAdmissionControl:
    def test_queue_full_typed_rejection(self, engine):
        sched, reg = _sched(engine, max_queue=2)
        ids = [sched.submit(Request(prompt=[1 + i], max_new_tokens=2))
               for i in range(5)]
        admitted = [r for r in ids if isinstance(r, int)]
        rejected = [r for r in ids if isinstance(r, Rejection)]
        assert len(admitted) == 2 and len(rejected) == 3
        for r in rejected:
            assert r.reason == "queue_full" and not r  # falsy by design
        assert len(sched.queue) == 2  # the bound held
        assert reg.snapshot()["serve/rejected"] == 3.0
        # the admitted requests still complete normally
        out = sched.run([])
        assert sorted(out) == sorted(admitted)

    def test_rejection_reason_vocabulary_closed(self):
        with pytest.raises(ValueError, match="reason"):
            Rejection("overloaded")

    def test_max_queue_validated(self, engine):
        with pytest.raises(ValueError, match="max_queue"):
            SlotScheduler(engine, registry=MetricsRegistry(), max_queue=0)

    def test_overload_2x_bounded_queue_and_goodput_ab(self, engine):
        """The overload contract (same-session A/B): at 2x sustained
        oversubmission with max_queue set, queue depth stays bounded,
        rejections are typed, and the in-SLO goodput of ADMITTED
        requests stays within 2x of the unloaded run's."""
        slo = [SLOTarget("e2e_ms", 95, 60000.0)]  # generous: CPU timing

        def tracker():
            return SLOTracker(slo, registry=MetricsRegistry(),
                              on_violation="skip")

        # unloaded: fewer requests than slots-worth of queue, no bound
        t_unloaded = tracker()
        sched, _ = _sched(engine, slo=t_unloaded)
        sched.run([Request(prompt=[1 + i], max_new_tokens=2)
                   for i in range(4)])
        unloaded_goodput = t_unloaded.goodput()

        # 2x oversubmission: a 3-token request holds its slot for 2
        # decode steps, so the 2-slot grid completes ~1 request/step —
        # and every step submits 2 fresh ones against a max_queue=2
        # bound: sustained offered load is 2x capacity
        t_loaded = tracker()
        sched, reg = _sched(engine, max_queue=2, slo=t_loaded)
        rejections, max_depth = [], 0
        for i in range(30):
            for j in range(2):
                r = sched.submit(Request(prompt=[1 + (i + j) % 90],
                                         max_new_tokens=3))
                if isinstance(r, Rejection):
                    rejections.append(r)
            sched.step()
            max_depth = max(max_depth, len(sched.queue))
        sched.run([])  # drain the tail
        assert max_depth <= 2, "queue depth exceeded max_queue"
        assert rejections and all(r.reason == "queue_full"
                                  for r in rejections)
        assert reg.snapshot()["serve/rejected"] == float(len(rejections))
        # admitted requests' goodput within a factor 2 of unloaded
        assert t_loaded.goodput() >= 0.5 * unloaded_goodput

    def test_run_paces_submissions_at_the_queue_bound(self, engine):
        """A closed batch knows its remaining work: run() holds
        queue_full'd requests host-side and resubmits as the queue
        drains — every request is eventually served while the bound
        holds throughout (silently dropping work a later step could
        serve would be a shedding decision the caller never made)."""
        sched, reg = _sched(engine, max_queue=1)
        out = sched.run([Request(prompt=[1 + i], max_new_tokens=2)
                         for i in range(4)])
        assert sorted(out) == [0, 1, 2, 3]
        assert all(c.finish_reason == "length" for c in out.values())
        # paced retries are NOT refused submissions: the counter an
        # operator alerts on must stay silent on a healthy closed batch
        assert reg.snapshot().get("serve/rejected", 0.0) == 0.0

    def test_run_drops_shed_requests(self, engine):
        """shed (brownout) rejections are final even inside run() —
        pacing applies only to queue_full backpressure."""
        tracker = _hot_tracker()
        sched, reg = _sched(engine,
                            brownout=BrownoutPolicy(tracker, shed=True))
        out = sched.run([Request(prompt=[1], max_new_tokens=2)])
        assert out == {}
        assert reg.snapshot()["serve/shed"] == 1.0


# ---------------------------------------------------------------------------
# deadlines + cancel
# ---------------------------------------------------------------------------

class TestDeadlines:
    def test_queued_expiry_never_spends_a_slot(self, engine):
        sched, reg = _sched(engine)
        for i in range(2):
            sched.submit(Request(prompt=[1 + i], max_new_tokens=4))
        rid = sched.submit(Request(prompt=[9], max_new_tokens=4,
                                   deadline_ms=1e-3))
        time.sleep(0.005)
        out = sched.run([])
        assert out[rid].finish_reason == "expired"
        assert out[rid].tokens == []
        snap = reg.snapshot()
        assert snap["serve/expired"] == 1.0
        assert snap["serve/admitted"] == 2.0  # the expired one never did

    def test_mid_flight_expiry_releases_slot(self, engine):
        sched, reg = _sched(engine)
        rid = sched.submit(Request(prompt=[1], max_new_tokens=500,
                                   deadline_ms=30.0))
        sched.step()  # admitted, first token sampled
        assert sched.active
        time.sleep(0.05)
        while sched.pending:
            sched.step()
        out = {c.request_id: c for c in sched.completed}
        assert out[rid].finish_reason == "expired"
        assert len(out[rid].tokens) >= 1  # partial output delivered
        assert not sched.active and sorted(sched.free) == [0, 1]
        np.testing.assert_array_equal(
            sched.engine.allocator.lengths, [0, 0])
        assert reg.snapshot()["serve/expired"] == 1.0

    def test_default_deadline_applies_when_request_sets_none(self, engine):
        sched, reg = _sched(engine, default_deadline_ms=1e-3)
        sched.submit(Request(prompt=[1], max_new_tokens=2))
        # a per-request deadline overrides the default
        ok = sched.submit(Request(prompt=[2], max_new_tokens=2,
                                  deadline_ms=60000.0))
        time.sleep(0.005)
        out = sched.run([])
        reasons = {k: v.finish_reason for k, v in out.items()}
        assert reasons[0] == "expired" and reasons[ok] == "length"

    def test_expired_requests_hurt_goodput(self, engine):
        """A queued expiry has NO measured ttft/tpot and a tiny e2e —
        it would sail under every latency target; the tracker must count
        server-side failure retirements against goodput unconditionally
        (FAILED_REASONS), or shedding the queue would READ as serving
        well."""
        tracker = SLOTracker([SLOTarget("e2e_ms", 95, 60000.0)],
                             registry=MetricsRegistry(),
                             on_violation="skip")
        sched, _ = _sched(engine, slo=tracker)
        for i in range(2):
            sched.submit(Request(prompt=[1 + i], max_new_tokens=2))
        sched.submit(Request(prompt=[9], max_new_tokens=2,
                             deadline_ms=1e-3))
        time.sleep(0.005)
        sched.run([])
        assert tracker.goodput() == pytest.approx(2.0 / 3.0)

    def test_cancel_queued_and_mid_flight(self, engine):
        sched, reg = _sched(engine)
        a = sched.submit(Request(prompt=[1], max_new_tokens=50))
        b = sched.submit(Request(prompt=[2], max_new_tokens=3))
        c = sched.submit(Request(prompt=[3], max_new_tokens=3))
        sched.step()  # a, b admitted; c queued
        assert sched.cancel(c)   # queued cancel
        assert sched.cancel(a)   # mid-flight cancel — slot freed
        assert not sched.cancel(a)   # idempotent: already gone
        assert not sched.cancel(999)  # unknown id
        sched.run([])
        out = {c_.request_id: c_ for c_ in sched.completed}
        reasons = {k: v.finish_reason for k, v in out.items()}
        assert reasons == {a: "cancelled", b: "length", c: "cancelled"}
        assert out[c].tokens == []
        assert reg.snapshot()["serve/cancelled"] == 2.0


class TestSubmitValidation:
    def test_nonpositive_deadline_raises(self, engine):
        sched, _ = _sched(engine)
        for bad in (0.0, -5.0):
            with pytest.raises(ValueError, match="deadline_ms"):
                sched.submit(Request(prompt=[1], deadline_ms=bad))
        assert sched.pending == 0

    def test_duplicate_in_flight_id_raises_then_reusable(self, engine):
        sched, _ = _sched(engine)
        sched.submit(Request(prompt=[1], max_new_tokens=2, request_id=7))
        with pytest.raises(ValueError, match="already in flight"):
            sched.submit(Request(prompt=[2], request_id=7))
        assert sched.pending == 1
        out = sched.run([])
        assert out[7].finish_reason == "length"
        # after completion the id is free again (replay/retry semantics)
        out = sched.run([Request(prompt=[3], max_new_tokens=2,
                                 request_id=7)])
        assert sorted(out) == [7]

    def test_default_deadline_validated(self, engine):
        with pytest.raises(ValueError, match="default_deadline_ms"):
            SlotScheduler(engine, registry=MetricsRegistry(),
                          default_deadline_ms=0.0)


# ---------------------------------------------------------------------------
# poison-slot quarantine
# ---------------------------------------------------------------------------

class TestQuarantine:
    def test_poison_retires_only_offending_slot(self, qengine, tmp_path):
        """The isolation contract: the injected poison step retires
        exactly one slot with finish_reason "poisoned"; every other
        request's greedy stream is identical to the fault-free run."""
        reqs = [Request(prompt=[5, 6], max_new_tokens=8),
                Request(prompt=[7, 8], max_new_tokens=8)]

        def run(plan, dump_dir):
            sched, reg = _sched(qengine, fault_plan=plan,
                                dump_dir=str(dump_dir))
            out = sched.run([Request(prompt=list(r.prompt),
                                     max_new_tokens=r.max_new_tokens)
                             for r in reqs])
            return out, reg, sched

        clean, _, _ = run(None, tmp_path / "clean")
        plan = FaultPlan(poison_logits={3: 0})
        faulted, reg, sched = run(plan, tmp_path / "faulted")

        assert faulted[0].finish_reason == "poisoned"
        # tokens up to the poison step were delivered; the NaN-step
        # token was discarded (prefill token + 2 decode ticks)
        assert faulted[0].tokens == clean[0].tokens[:3]
        # the neighbor's stream is IDENTICAL to the fault-free run
        assert faulted[1].finish_reason == "length"
        assert faulted[1].tokens == clean[1].tokens
        assert reg.snapshot()["serve/poisoned"] == 1.0
        # the slot was released (cursor zeroed) like any retirement
        np.testing.assert_array_equal(
            qengine.allocator.lengths, [0, 0])

    def test_poison_writes_strict_json_flight_record(self, qengine,
                                                     tmp_path):
        trace = RequestTrace(capacity=8)
        sched = SlotScheduler(qengine, registry=MetricsRegistry(),
                              trace=trace,
                              fault_plan=FaultPlan(poison_logits={2: 1}),
                              dump_dir=str(tmp_path))
        sched.run([Request(prompt=[3, 4], max_new_tokens=6),
                   Request(prompt=[5, 6], max_new_tokens=6)])
        assert len(sched.poison_dumps) == 1
        with open(sched.poison_dumps[0]) as f:
            doc = json.load(f)  # strict JSON by construction
        assert doc["config"]["finish_reason"] == "poisoned"
        assert doc["config"]["slot"] == 1
        recs = doc["requests"]
        assert any(r["finish_reason"] == "poisoned" for r in recs)

    def test_poison_plan_refused_on_plain_engine(self, engine):
        with pytest.raises(ValueError, match="quarantine"):
            SlotScheduler(engine, registry=MetricsRegistry(),
                          fault_plan=FaultPlan(poison_logits={1: 0}))
        with pytest.raises(ValueError, match="quarantine"):
            engine.decode(np.zeros(2, np.int32), np.zeros(2, np.float32),
                          poison=np.zeros(2, np.float32))

    def test_quarantine_engine_serves_identically_unpoisoned(
            self, engine, qengine):
        """The quarantine check observes, never perturbs: an unpoisoned
        run on the quarantine engine produces the same greedy streams as
        the plain engine."""
        reqs = [Request(prompt=[11, 12, 13], max_new_tokens=5),
                Request(prompt=[14], max_new_tokens=5)]
        out_plain = SlotScheduler(engine, registry=MetricsRegistry()).run(
            [Request(prompt=list(r.prompt),
                     max_new_tokens=r.max_new_tokens) for r in reqs])
        out_q = SlotScheduler(qengine, registry=MetricsRegistry()).run(
            [Request(prompt=list(r.prompt),
                     max_new_tokens=r.max_new_tokens) for r in reqs])
        for rid in out_plain:
            assert out_plain[rid].tokens == out_q[rid].tokens


# ---------------------------------------------------------------------------
# zero-cost off + zero-recompile contracts
# ---------------------------------------------------------------------------

class TestZeroCostOff:
    def test_programs_byte_identical_with_resilience_off(
            self, model_params, engine):
        """The established zero-cost idiom: resilience features OFF
        (quarantine off at the engine, no scheduler knobs) leaves all
        three AOT programs byte-identical to a freshly-built baseline
        engine's."""
        model, params = model_params
        fresh = ServingEngine(model, params, max_seqs=2, max_len=32,
                              prefill_len=8)
        for a, b in ((engine.prefill_compiled, fresh.prefill_compiled),
                     (engine.decode_compiled, fresh.decode_compiled),
                     (engine.release_compiled, fresh.release_compiled)):
            assert program_text(a) == program_text(b)

    def test_host_side_knobs_leave_programs_untouched(self, model_params,
                                                      engine):
        """max_queue / deadlines / brownout / flood plans are pure host
        policy: a scheduler wired with all of them drives byte-identical
        programs with zero recompiles."""
        model, params = model_params
        wired_eng = ServingEngine(model, params, max_seqs=2, max_len=32,
                                  prefill_len=8)
        tracker = SLOTracker([SLOTarget("ttft_ms", 95, 60000.0)],
                             registry=MetricsRegistry(),
                             on_violation="skip")
        sched = SlotScheduler(
            wired_eng, registry=MetricsRegistry(), slo=tracker,
            max_queue=8, default_deadline_ms=60000.0,
            brownout=BrownoutPolicy(tracker, cap_max_new_tokens=64),
            fault_plan=FaultPlan(flood={2: 1}))
        out = sched.run([Request(prompt=[1 + i], max_new_tokens=3)
                         for i in range(3)], no_recompile=True)
        assert sorted(out) == [0, 1, 2]
        for a, b in ((engine.prefill_compiled, wired_eng.prefill_compiled),
                     (engine.decode_compiled, wired_eng.decode_compiled),
                     (engine.release_compiled,
                      wired_eng.release_compiled)):
            assert program_text(a) == program_text(b)

    def test_quarantine_differs_only_in_decode(self, engine, qengine):
        assert (program_text(engine.prefill_compiled)
                == program_text(qengine.prefill_compiled))
        assert (program_text(engine.release_compiled)
                == program_text(qengine.release_compiled))
        assert (program_text(engine.decode_compiled)
                != program_text(qengine.decode_compiled))

    def test_poison_injection_never_recompiles(self, qengine):
        """Injecting (and clearing) poison is an array-argument change on
        the already-compiled quarantine program — flat compile counters
        across a run that poisons mid-flight."""
        sched = SlotScheduler(qengine, registry=MetricsRegistry(),
                              fault_plan=FaultPlan(poison_logits={2: 0}),
                              dump_dir="/tmp")
        out = sched.run([Request(prompt=[2, 3], max_new_tokens=6),
                         Request(prompt=[4, 5], max_new_tokens=6)],
                        no_recompile=True)
        assert out[0].finish_reason == "poisoned"
        assert out[1].finish_reason == "length"


# ---------------------------------------------------------------------------
# graceful drain + hot weight swap
# ---------------------------------------------------------------------------

class TestDrain:
    def test_drain_finishes_in_flight_keeps_queued(self, engine):
        sched, reg = _sched(engine)
        for i in range(4):
            sched.submit(Request(prompt=[1 + i], max_new_tokens=4))
        sched.step()  # 2 admitted, 2 queued
        done = sched.drain()
        assert sorted(done) == [0, 1]
        assert all(c.finish_reason == "length" for c in done.values())
        assert len(sched.queue) == 2  # queued survive for after the swap
        assert reg.snapshot()["serve/drains"] == 1.0
        # admission resumes after the drain returns
        assert isinstance(sched.submit(Request(prompt=[9],
                                               max_new_tokens=2)), int)
        sched.run([])  # leave the shared engine clean

    def test_submit_during_drain_rejected(self, engine, monkeypatch):
        sched, reg = _sched(engine)
        sched.submit(Request(prompt=[1], max_new_tokens=3))
        sched.step()
        seen = {}

        # observe the draining flag from inside the drain loop via the
        # step path itself
        orig_step = sched.step

        def probing_step():
            r = sched.submit(Request(prompt=[5], max_new_tokens=1))
            seen["rejection"] = r
            return orig_step()

        monkeypatch.setattr(sched, "step", probing_step)
        sched.drain()
        assert isinstance(seen["rejection"], Rejection)
        assert seen["rejection"].reason == "draining"
        assert reg.snapshot()["serve/rejected"] >= 1.0

    def test_drain_deadline_expires_leftovers(self, engine):
        """A drain running out of budget is the SERVER dropping accepted
        work: leftovers retire "expired" (a FAILED_REASONS member, so a
        lossy rollover shows up in goodput), not "cancelled" (which
        means the user walked away)."""
        tracker = SLOTracker([SLOTarget("e2e_ms", 95, 60000.0)],
                             registry=MetricsRegistry(),
                             on_violation="skip")
        sched, reg = _sched(engine, slo=tracker)
        rid = sched.submit(Request(prompt=[1], max_new_tokens=100000))
        sched.step()
        done = sched.drain(deadline_s=0.0)  # never finishes in time
        assert done[rid].finish_reason == "expired"
        assert not sched.active and sorted(sched.free) == [0, 1]
        assert reg.snapshot()["serve/expired"] == 1.0
        assert tracker.goodput() == 0.0  # the lossy drain is visible


class TestHotSwap:
    def _engine(self, model_params, **kw):
        model, params = model_params
        return ServingEngine(model, params, max_seqs=2, max_len=32,
                             prefill_len=8, **kw), model, params

    def test_swap_mid_run_completes_in_flight_and_changes_outputs(
            self, model_params):
        """The hot-swap contract: swap_params mid-loop completes
        in-flight requests, subsequent outputs come from the NEW
        weights, and the compile-storm counters stay flat (zero
        recompiles) with donation re-linted on the swap."""
        from apex_tpu.analysis.program import recompile_guard

        eng, model, params = self._engine(model_params)
        # a fresh init, not a scalar multiple of the old weights
        # (layernorm makes uniformly-scaled params nearly
        # argmax-invariant) — and the probe prompt is SEARCHED for one
        # where the two weight sets disagree on the first greedy token,
        # because a tiny random model can decode the same degenerate
        # repetition stream under unrelated inits
        new_params = model.init(jax.random.PRNGKey(123))

        def nxt(p, toks):
            return int(np.argmax(np.asarray(
                model(p, jnp.asarray([toks]))[0, -1])))

        prompt = next(t for t in ([1 + i, 2 + i, 3 + i]
                                  for i in range(60))
                      if nxt(params, t) != nxt(new_params, t))
        sched, reg = _sched(eng)

        # reference streams for the probe prompt under each weight set
        ref_old = SlotScheduler(eng, registry=MetricsRegistry()).run(
            [Request(prompt=list(prompt), max_new_tokens=6)])[0].tokens
        eng2, _, _ = self._engine(model_params)
        eng2.swap_params(new_params)
        ref_new = SlotScheduler(eng2, registry=MetricsRegistry()).run(
            [Request(prompt=list(prompt), max_new_tokens=6)])[0].tokens
        assert ref_old != ref_new  # guaranteed by the probe search

        mid = sched.submit(Request(prompt=[7, 8], max_new_tokens=12))
        sched.step()
        sched.step()
        with recompile_guard("hot swap") as guard:
            sched.step()
            guard.rebase()  # host paths warm; the swap must stay flat
            sched.swap_params(new_params)
            while sched.pending:
                sched.step()
            post = sched.run([Request(prompt=list(prompt),
                                      max_new_tokens=6, request_id=50)])
        out = {c.request_id: c for c in sched.completed}
        # the in-flight request completed across the swap
        assert out[mid].finish_reason == "length"
        assert len(out[mid].tokens) == 12
        # a post-swap request decodes the NEW weights' stream exactly
        assert post[50].tokens == ref_new
        assert reg.snapshot()["serve/swaps"] == 1.0
        assert eng.swaps == 1

    def test_swap_shape_and_structure_mismatches_refused(
            self, model_params):
        eng, model, params = self._engine(model_params)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        with pytest.raises(ValueError, match="structure"):
            eng.swap_params(leaves)  # a list is not the params tree
        bad = jax.tree_util.tree_unflatten(
            treedef, [jnp.zeros((3, 3), jnp.float32) for _ in leaves])
        with pytest.raises(ValueError, match="never retrace"):
            eng.swap_params(bad)
        # the engine still serves with its original weights
        out = SlotScheduler(eng, registry=MetricsRegistry()).run(
            [Request(prompt=[1], max_new_tokens=2)])
        assert out[0].finish_reason == "length"


class TestCheckpointWatcher:
    def test_rolls_onto_latest_committed_only(self, model_params,
                                              tmp_path):
        from apex_tpu.checkpoint import save_checkpoint

        eng, model, params = TestHotSwap()._engine(model_params)
        reg = MetricsRegistry()
        run_dir = str(tmp_path)
        watcher = CheckpointWatcher(eng, run_dir, registry=reg)
        assert watcher.poll() is None  # no checkpoint yet: keep serving

        p1 = jax.tree_util.tree_map(lambda x: x * 1.5, params)
        save_checkpoint(run_dir, p1, 1)
        assert watcher.poll() == 1
        assert watcher.poll() is None  # nothing new
        np.testing.assert_allclose(
            np.asarray(jax.tree_util.tree_leaves(eng.params)[0]),
            np.asarray(jax.tree_util.tree_leaves(p1)[0]), rtol=1e-6)

        # a torn step (no COMMITTED marker) is invisible; the watcher
        # rolls onto the newest COMMITTED step beneath it
        p2 = jax.tree_util.tree_map(lambda x: x * 0.5, params)
        save_checkpoint(run_dir, p2, 2)
        (tmp_path / "step_00000003").mkdir()
        assert watcher.poll() == 2
        assert reg.snapshot()["serve/swaps"] == 2.0

    def test_watch_checkpoints_polls_immediately(self, model_params,
                                                 tmp_path):
        from apex_tpu.checkpoint import save_checkpoint

        eng, model, params = TestHotSwap()._engine(model_params)
        p1 = jax.tree_util.tree_map(lambda x: x + 0.25, params)
        save_checkpoint(str(tmp_path), p1, 5)
        watcher = watch_checkpoints(eng, str(tmp_path))
        assert watcher.step == 5


# ---------------------------------------------------------------------------
# SLO-driven brownout
# ---------------------------------------------------------------------------

def _hot_tracker(threshold_ms=1.0, n=16):
    """A tracker whose window is saturated with over-threshold e2e
    observations — burn rate far above 1."""
    from apex_tpu.observability.reqtrace import RequestRecord

    tracker = SLOTracker([SLOTarget("e2e_ms", 95, threshold_ms)],
                         registry=MetricsRegistry(), on_violation="skip")
    for i in range(n):
        rec = RequestRecord(request_id=i, prompt_len=1, submit_t=0.0)
        rec.retire_t = 10.0  # e2e = 10000 ms >> threshold
        tracker.observe(rec)
    return tracker


class TestBrownout:
    def test_shed_on_burn_rate_over_threshold(self, engine):
        tracker = _hot_tracker()
        assert tracker.max_burn_rate() > 1.0
        sched, reg = _sched(engine,
                            brownout=BrownoutPolicy(tracker, shed=True))
        r = sched.submit(Request(prompt=[1], max_new_tokens=4))
        assert isinstance(r, Rejection) and r.reason == "shed"
        snap = reg.snapshot()
        assert snap["serve/shed"] == 1.0
        assert snap["serve/brownout"] == 1.0

    def test_cap_max_new_tokens_instead_of_shedding(self, engine):
        tracker = _hot_tracker()
        policy = BrownoutPolicy(tracker, shed=False, cap_max_new_tokens=2)
        sched, reg = _sched(engine, brownout=policy)
        rid = sched.submit(Request(prompt=[1], max_new_tokens=50))
        assert isinstance(rid, int)
        out = sched.run([])
        # graceful degradation: served, but short
        assert out[rid].finish_reason == "length"
        assert len(out[rid].tokens) == 2

    def test_cold_window_never_engages(self, engine):
        tracker = SLOTracker([SLOTarget("e2e_ms", 95, 1.0)],
                             registry=MetricsRegistry(),
                             on_violation="skip")
        sched, reg = _sched(engine,
                            brownout=BrownoutPolicy(tracker, shed=True))
        rid = sched.submit(Request(prompt=[1], max_new_tokens=2))
        assert isinstance(rid, int)  # NaN burn (empty window) admits
        assert reg.snapshot()["serve/brownout"] == 0.0
        sched.run([])

    def test_policy_validation(self):
        tracker = _hot_tracker()
        with pytest.raises(ValueError, match="burn_threshold"):
            BrownoutPolicy(tracker, burn_threshold=0.0)
        with pytest.raises(ValueError, match="cap_max_new_tokens"):
            BrownoutPolicy(tracker, cap_max_new_tokens=0)
        with pytest.raises(ValueError, match="nothing"):
            BrownoutPolicy(tracker, shed=False)


# ---------------------------------------------------------------------------
# exception safety
# ---------------------------------------------------------------------------

class TestExceptionSafety:
    def test_decode_fault_retires_in_flight_and_reraises(self, engine,
                                                         monkeypatch):
        sched, reg = _sched(engine)
        a = sched.submit(Request(prompt=[1], max_new_tokens=9))
        b = sched.submit(Request(prompt=[2], max_new_tokens=9))
        sched.step()
        assert len(sched.active) == 2

        def boom(*args, **kw):
            raise RuntimeError("injected decode fault")

        monkeypatch.setattr(engine, "decode", boom)
        with pytest.raises(RuntimeError, match="injected decode fault"):
            sched.step()
        # nothing stranded: records retired, slots released, loop usable
        assert not sched.active and sorted(sched.free) == [0, 1]
        out = {c.request_id: c for c in sched.completed}
        assert out[a].finish_reason == "error"
        assert out[b].finish_reason == "error"
        assert len(out[a].tokens) >= 1  # partial output still delivered
        assert reg.snapshot()["serve/errors"] == 2.0
        monkeypatch.undo()
        post = sched.run([Request(prompt=[3], max_new_tokens=2)])
        assert len(post) == 1

    def test_prefill_fault_retires_popped_request(self, engine,
                                                  monkeypatch):
        sched, reg = _sched(engine)
        rid = sched.submit(Request(prompt=[1], max_new_tokens=4))

        def boom(*args, **kw):
            raise RuntimeError("injected prefill fault")

        monkeypatch.setattr(engine, "prefill", boom)
        with pytest.raises(RuntimeError, match="injected prefill fault"):
            sched.step()
        assert sorted(sched.free) == [0, 1]  # the popped slot came back
        out = {c.request_id: c for c in sched.completed}
        assert out[rid].finish_reason == "error"
        assert reg.snapshot()["serve/errors"] == 1.0
        monkeypatch.undo()
        assert len(sched.run([Request(prompt=[2],
                                      max_new_tokens=2)])) == 1


# ---------------------------------------------------------------------------
# FaultPlan serving faults + the chaos run
# ---------------------------------------------------------------------------

class TestServingFaultPlan:
    def test_json_round_trip(self):
        plan = FaultPlan(poison_logits={4: 1}, slow_decode_s=0.25,
                         flood={2: 6}, seed=9)
        assert FaultPlan.from_json(plan.to_json()) == plan

    def test_sample_serving_deterministic_and_bounded(self):
        a = FaultPlan.sample_serving(23, 10, max_slots=2)
        b = FaultPlan.sample_serving(23, 10, max_slots=2)
        assert a == b and a.seed == 23
        for seed in range(20):
            p = FaultPlan.sample_serving(seed, 12, max_slots=4,
                                         flood_n=3)
            (fstep, fn), = p.flood.items()
            (pstep, pslot), = p.poison_logits.items()
            assert 1 <= fstep < 3 and fn == 3
            assert 6 <= pstep < 12 and 0 <= pslot < 4
            assert FaultPlan.from_json(p.to_json()) == p

    def test_sample_serving_validation(self):
        with pytest.raises(ValueError, match="total_steps"):
            FaultPlan.sample_serving(0, 3, max_slots=2)
        with pytest.raises(ValueError, match="max_slots"):
            FaultPlan.sample_serving(0, 8, max_slots=0)

    def test_slow_decode_stretches_steps(self, engine):
        sched, _ = _sched(engine,
                          fault_plan=FaultPlan(slow_decode_s=0.02))
        t0 = time.perf_counter()
        sched.run([Request(prompt=[1], max_new_tokens=4)])
        assert time.perf_counter() - t0 >= 3 * 0.02  # 3 decode steps


class TestChaosRun:
    """The deterministic chaos leg: flood + poison + slow step in ONE
    FaultPlan.sample_serving-driven run — bounded queue, only the
    poisoned slot retired, every other greedy stream identical to the
    fault-free run, flat compile counters under recompile_guard."""

    SEED = 23  # sample_serving(23, 10, max_slots=2):
    #            flood at an early step, poison in [5, 10)

    def _drive(self, qengine, plan, max_queue):
        reg = MetricsRegistry()
        sched = SlotScheduler(qengine, registry=reg, max_queue=max_queue,
                              fault_plan=plan, dump_dir="/tmp")
        rng = np.random.RandomState(0)

        def fresh(i):
            return Request(prompt=[1 + int(rng.randint(90)), 2],
                           max_new_tokens=10, request_id=100 + i)

        for i in range(4):
            sched.submit(fresh(i))
        submitted, rejections, max_depth = 4, [], 0
        while sched.pending:
            if plan is not None:
                for _ in range(plan.flood_n(sched.steps + 1)):
                    r = sched.submit(fresh(submitted))
                    submitted += 1
                    if isinstance(r, Rejection):
                        rejections.append(r)
            sched.step()
            max_depth = max(max_depth, len(sched.queue))
        return sched, reg, rejections, max_depth

    def test_flood_poison_slow_in_one_run(self, qengine):
        plan = FaultPlan.sample_serving(self.SEED, 10, max_slots=2,
                                        flood_n=6, slow_decode_s=0.002)
        # the identical request schedule, faults stripped: the flood
        # still happens (same driver), poison/slow removed
        clean_plan = FaultPlan(flood=dict(plan.flood))

        clean, *_ = self._drive(qengine, clean_plan, max_queue=4)
        sched, reg, rejections, max_depth = self._drive(
            qengine, plan, max_queue=4)

        # bounded queue + typed rejections under the flood
        assert max_depth <= 4
        assert rejections and all(r.reason == "queue_full"
                                  for r in rejections)
        # exactly one poisoned retirement...
        snap = reg.snapshot()
        assert snap["serve/poisoned"] == 1.0
        poisoned = [c for c in sched.completed
                    if c.finish_reason == "poisoned"]
        assert len(poisoned) == 1
        # ...and every other completed request's greedy stream is
        # byte-identical to the fault-free run's
        clean_out = {c.request_id: c for c in clean.completed}
        for c in sched.completed:
            if c.finish_reason == "poisoned" or c.request_id \
                    not in clean_out:
                continue
            if clean_out[c.request_id].finish_reason == "length":
                assert c.tokens == clean_out[c.request_id].tokens, \
                    c.request_id

    def test_chaos_run_zero_recompiles(self, qengine):
        from apex_tpu.analysis.program import recompile_guard

        plan = FaultPlan.sample_serving(self.SEED, 10, max_slots=2,
                                        flood_n=4)
        reg = MetricsRegistry()
        sched = SlotScheduler(qengine, registry=reg, max_queue=4,
                              fault_plan=plan, dump_dir="/tmp")
        for i in range(4):
            sched.submit(Request(prompt=[3 + i, 4], max_new_tokens=10))
        with recompile_guard("chaos") as guard:
            first = True
            while sched.pending:
                for _ in range(plan.flood_n(sched.steps + 1)):
                    sched.submit(Request(prompt=[7, 8],
                                         max_new_tokens=10))
                sched.step()
                if first:
                    guard.rebase()
                    first = False
        assert reg.snapshot()["serve/poisoned"] >= 1.0

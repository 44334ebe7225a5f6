"""Timers + profiling-annotation tests
(``reference:apex/transformer/pipeline_parallel/_timers.py:6-79``)."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.utils.timers import Timer, Timers, device_fence


def test_timer_accumulates_and_resets():
    t = Timer("t")
    t.start()
    time.sleep(0.01)
    t.stop()
    t.start()
    time.sleep(0.01)
    t.stop()
    assert t.count_ == 2
    elapsed = t.elapsed(reset=True)
    assert elapsed >= 0.02
    assert t.elapsed(reset=False) == 0.0


def test_timer_elapsed_while_running_restarts():
    t = Timer("t")
    t.start()
    time.sleep(0.005)
    first = t.elapsed(reset=False)
    assert first > 0
    assert t.started_  # still running, like the reference
    t.stop()


def test_timer_context_manager_and_fence():
    t = Timer("t")
    x = jnp.ones((256, 256))
    with t(wait_for=None):
        y = jax.jit(lambda a: a @ a)(x)
        device_fence(y)
    assert t.elapsed() > 0


def test_timers_group_log_and_write():
    ts = Timers()
    ts("fwd").start()
    time.sleep(0.002)
    ts("fwd").stop()
    line = ts.log(["fwd"], reset=False)
    assert line.startswith("time (ms) | fwd:")

    class FakeWriter:
        def __init__(self):
            self.calls = []

        def add_scalar(self, tag, value, step):
            self.calls.append((tag, value, step))

    w = FakeWriter()
    ts.write(["fwd"], w, iteration=3)
    assert w.calls and w.calls[0][0] == "fwd-time" and w.calls[0][2] == 3


def test_named_scopes_reach_hlo():
    """The pre-annotated hot paths must show up in lowered HLO metadata —
    that is what makes a captured profile attributable (the pyprof
    annotate-step equivalent)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.parallel.distributed import allreduce_grads
    from apex_tpu.parallel.sync_batchnorm import (BatchNormState,
                                                  sync_batch_norm)

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def scope_text(lowered):
        """Render with op metadata: newer jax carries scopes in the
        lowered text under debug_info=True; 0.4.x only in compiled HLO."""
        try:
            return lowered.as_text(debug_info=True)
        except TypeError:
            return lowered.compile().as_text()

    def step(g):
        return shard_map(
            lambda g: allreduce_grads({"w": g}, "data")["w"],
            mesh=mesh, in_specs=P("data"), out_specs=P("data"))(g)

    txt = scope_text(jax.jit(step).lower(jnp.ones((2, 4))))
    assert "apex_ddp_allreduce" in txt

    state = BatchNormState(jnp.zeros(3), jnp.ones(3), jnp.asarray(0))

    def bn(x):
        return sync_batch_norm(x, jnp.ones(3), jnp.zeros(3), state,
                               channel_axis=-1)[0]

    txt = scope_text(jax.jit(bn).lower(jnp.ones((4, 3))))
    assert "sync_bn_stats" in txt


class TestAutoResume:
    def test_sigterm_sets_flag(self):
        import os
        import signal

        from apex_tpu.utils.autoresume import AutoResume
        with AutoResume(interval=10) as ar:
            assert not ar.termination_requested(step=0)
            prev = signal.getsignal(signal.SIGTERM)
            os.kill(os.getpid(), signal.SIGTERM)
            assert ar.termination_requested(step=3)  # flag beats interval
        # context exit restored the previous handler
        assert signal.getsignal(signal.SIGTERM) is not prev

    def test_env_and_hook_polling(self, monkeypatch):
        from apex_tpu.utils.autoresume import AutoResume
        calls = []

        def hook():
            calls.append(1)
            return False

        ar = AutoResume(interval=5, hook=hook,
                        install_sigterm_handler=False)
        for s in range(1, 5):
            assert not ar.termination_requested(step=s)
        assert not calls  # off-interval steps do not poll
        ar.termination_requested(step=5)
        assert len(calls) == 1
        monkeypatch.setenv("APEX_TPU_TERMINATE", "1")
        assert ar.termination_requested(step=10)

    def test_checkpoint_then_resume_flow(self, tmp_path, monkeypatch):
        """The documented recipe: terminate -> checkpoint -> restart ->
        restore latest."""
        import jax.numpy as jnp
        import pytest

        from apex_tpu.checkpoint import restore_checkpoint, save_checkpoint
        from apex_tpu.utils.autoresume import AutoResume

        ar = AutoResume(install_sigterm_handler=False)
        monkeypatch.setenv("APEX_TPU_TERMINATE", "1")
        state = {"w": jnp.ones(4) * 7}
        if ar.termination_requested(step=12):
            save_checkpoint(str(tmp_path), state, step=12,
                            host_state={"step": 12})
            with pytest.raises(SystemExit):
                ar.request_resume()
        restored, host = restore_checkpoint(str(tmp_path), state)
        assert host["step"] == 12
        assert float(restored["w"][0]) == 7.0

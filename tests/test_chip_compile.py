"""The chip's compiler, asked without the chip.

The TPU compiler is installed in the CPU sandbox and compiles for a
DESCRIBED v5e device. Each case lowers one kernel of the main path at the
GPT-small serving/training widths and compiles it: what Mosaic refuses
(block shapes off the (8, 128) tiling, too much VMEM) fails here at no
chip time. Interpret-mode tests cannot see any of this.

The topology is described only inside the module-scoped fixture below —
never at import, in a ``skipif`` or in ``parametrize`` arguments: one
process at a time may load libtpu, and every xdist worker imports every
test file. Keep all such compiles in THIS file (a second file could land
on another worker, where the fixture would skip).
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the package re-exports the FUNCTION under the module's name
fa = importlib.import_module("apex_tpu.ops.flash_attention")

# GPT-small: 12 heads x head dim 64, seq/max_len 1024, 8 serving slots;
# paged pool = BENCH_DECODE_CONFIGS["gpt_decode_paged"] (bench.py)
B, H, S, D = 4, 12, 1024, 64
SLOTS, MAX_LEN, BLOCK, NUM_BLOCKS = 8, 1024, 128, 65
VERIFY_Q = 5                      # speculate_k=4 drafts + the bonus row


@pytest.fixture(scope="module")
def topo():
    """Skips only where the TPU compiler cannot be loaded (no libtpu, or
    another process holds its lock: both reach here as a RuntimeError).
    Nothing but the one jax call is inside the ``try``, so a kernel the
    compiler refuses, or a topologies API that moved, fails instead."""
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except RuntimeError as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_tpu(monkeypatch):
    """Steer the kernels' backend probe to "tpu" (the process's default
    backend is the CPU, which would pick interpret mode) and keep the
    persistent compile cache out of these compiles: an executable for a
    described device is written to it but can never be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.setattr(fa, "_interp", lambda: False)
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


BF16, F32, I8, I32 = jnp.bfloat16, jnp.float32, jnp.int8, jnp.int32
QKV = [((B, H, S, D), BF16)] * 3


def _flash_fwd(q, k, v):
    return fa.flash_attention(q, k, v, causal=True, use_pallas=True)


def _flash_loss(dropout_rate):
    def loss(q, k, v):
        out = fa.flash_attention(
            q, k, v, causal=True, use_pallas=True,
            dropout_rate=dropout_rate,
            dropout_seed=7 if dropout_rate else None)
        return jnp.sum(out.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


def _dense(q_len, quantized):
    qs = (SLOTS, H, D) if q_len == 1 else (SLOTS, H, q_len, D)
    cache = (SLOTS, H, MAX_LEN, D)
    shapes = [(qs, BF16), (cache, I8 if quantized else BF16),
              (cache, I8 if quantized else BF16), ((SLOTS,), I32),
              (qs, BF16), (qs, BF16)]
    if quantized:
        shapes += [((SLOTS, H, MAX_LEN), F32)] * 2

    def f(q, k, v, lengths, k_new, v_new, k_scale=None, v_scale=None):
        return fa.decode_attention(q, k, v, lengths, k_new=k_new,
                                   v_new=v_new, k_scale=k_scale,
                                   v_scale=v_scale, use_pallas=True)
    return f, shapes


def _paged(q_len, quantized):
    qs = (SLOTS, H, D) if q_len == 1 else (SLOTS, H, q_len, D)
    pool = (NUM_BLOCKS, H, BLOCK, D)
    shapes = [(qs, BF16), (pool, I8 if quantized else BF16),
              (pool, I8 if quantized else BF16),
              ((SLOTS, MAX_LEN // BLOCK), I32), ((SLOTS,), I32),
              (qs, BF16), (qs, BF16)]
    if quantized:
        shapes += [((NUM_BLOCKS, H, BLOCK), F32)] * 2

    def f(q, kp, vp, tables, lengths, k_new, v_new, k_scale=None,
          v_scale=None):
        return fa.paged_decode_attention(
            q, kp, vp, tables, lengths, k_new=k_new, v_new=v_new,
            k_scale=k_scale, v_scale=v_scale, mean_context=160.0,
            use_pallas=True)
    return f, shapes


CASES = {
    "flash_fwd": lambda: (_flash_fwd, QKV),
    "flash_fwd_bwd": lambda: (_flash_loss(0.0), QKV),
    "flash_fwd_bwd_dropout": lambda: (_flash_loss(0.1), QKV),
    "dense_decode_q1": lambda: _dense(1, False),
    "dense_verify_q5": lambda: _dense(VERIFY_Q, False),
    "dense_decode_int8": lambda: _dense(1, True),
    "paged_decode_q1": lambda: _paged(1, False),
    "paged_verify_q5": lambda: _paged(VERIFY_Q, False),
    "paged_decode_int8": lambda: _paged(1, True),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, for_tpu):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()

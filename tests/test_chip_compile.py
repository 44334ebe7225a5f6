"""The chip's compiler, asked without the chip.

The TPU compiler is installed in the CPU sandbox and compiles for a
DESCRIBED v5e device. Each kernel case lowers one kernel of the main path
at the GPT-small serving/training widths and compiles it: what Mosaic
refuses (block shapes off the (8, 128) tiling, too much VMEM) fails here
at no chip time. Interpret-mode tests cannot see any of this. The paged
STEP cases compile the serve cell's whole programs at gpt2-large widths
and read the compiler's memory plan: the pool is updated where it lies,
or the case fails.

The topology is described only inside the module-scoped fixture below —
never at import, in a ``skipif`` or in ``parametrize`` arguments: one
process at a time may load libtpu, and every xdist worker imports every
test file. Keep all such compiles in THIS file (a second file could land
on another worker, where the fixture would skip).
"""
import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the package re-exports the FUNCTION under the module's name
fa = importlib.import_module("apex_tpu.ops.flash_attention")

# GPT-small: 12 heads x head dim 64, seq/max_len 1024, 8 serving slots;
# paged pool = BENCH_DECODE_CONFIGS["gpt_decode_paged"] (bench.py)
B, H, S, D = 4, 12, 1024, 64
SLOTS, MAX_LEN, BLOCK, NUM_BLOCKS, LAYERS = 8, 1024, 128, 65, 12
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


# the benchmark cells' own shapes: gpt2-medium's microbatch (4 x 16 heads x
# 1,024 x 64, forward and backward), gpt2-large's prefill bucket (20 heads
# x 512 x 64, forward); and a head too long for the default VMEM budget of
# the backward, which holds one head's dq
MEDIUM = [((4, 16, 1024, 64), BF16)] * 3
LARGE_PREFILL = [((1, 20, 512, 64), BF16)] * 3
LONG_HEAD = [((1, 2, 8192, 128), BF16)] * 3


def _flash_loss(dropout_rate):
    def loss(q, k, v):
        out = fa.flash_attention(
            q, k, v, causal=True, use_pallas=True,
            dropout_rate=dropout_rate,
            dropout_seed=7 if dropout_rate else None)
        return jnp.sum(out.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


def _paged(q_len, quantized, heads=H, head_dim=D, block=BLOCK):
    # the pool as PagedKVCache stores it, all layers stacked; the kernel
    # is aimed at one of them by a traced index, as in the layer scan
    qs = (SLOTS, heads, head_dim) if q_len == 1 \
        else (SLOTS, heads, q_len, head_dim)
    pool = (LAYERS, NUM_BLOCKS, block, heads * head_dim)
    shapes = [(qs, BF16), (pool, I8 if quantized else BF16),
              (pool, I8 if quantized else BF16), ((), I32),
              ((SLOTS, MAX_LEN // block), I32), ((SLOTS,), I32),
              (qs, BF16), (qs, BF16)]
    if quantized:
        shapes += [((LAYERS, NUM_BLOCKS, heads, block), F32)] * 2

    def f(q, kp, vp, layer, tables, lengths, k_new, v_new, k_scale=None,
          v_scale=None):
        return fa.paged_decode_attention(
            q, kp, vp, layer, tables, lengths, k_new=k_new, v_new=v_new,
            k_scale=k_scale, v_scale=v_scale, mean_context=160.0,
            use_pallas=True)
    return f, shapes


CASES = {
    "flash_fwd": lambda: (_flash_fwd, QKV),
    "flash_fwd_bwd": lambda: (_flash_loss(0.0), QKV),
    "flash_fwd_bwd_dropout": lambda: (_flash_loss(0.1), QKV),
    "flash_fwd_bwd_medium": lambda: (_flash_loss(0.0), MEDIUM),
    "flash_fwd_large_prefill": lambda: (_flash_fwd, LARGE_PREFILL),
    "flash_fwd_bwd_long_head": lambda: (_flash_loss(0.0), LONG_HEAD),
    "paged_decode_q1": lambda: _paged(1, False),
    "paged_verify_q5": lambda: _paged(VERIFY_Q, False),
    "paged_decode_int8": lambda: _paged(1, True),
    # no shape is refused: 3 heads x 40 = 120 lanes of 128, blocks of 8
    # tokens (what an engine's default block is under a bucket of 8)
    "paged_decode_off_lanes": lambda: _paged(1, False, heads=3, head_dim=40,
                                             block=8),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, for_tpu):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    assert "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def test_training_flash_kernels_keep_the_name_the_metric_reads(one_chip,
                                                               for_tpu):
    """``flash_attention_roofline.train`` finds its events by
    ``^flash_attention[.\\d]* `` inside the step: the instruction names come
    from ``jax.named_scope("flash_attention")`` and no ``name=`` on the
    training ``pallas_call``s. A kernel the pattern missed would put the
    whole work over the others' time alone and read over 100%."""
    fn, shapes = CASES["flash_fwd_bwd_medium"]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2, calls      # one forward, ONE backward
    for line in calls:
        name = line.split("=")[0].strip(" %")
        scope = re.search(r'op_name="([^"]*)"', line).group(1)
        assert "flash_attention" in name and "window" not in name, name
        assert re.search(r"\(flash_attention\)+/pallas_call$", scope), scope


# ---------------------------------------------------------------------------
# the paged serving programs, whole, at the serve cell's size
# ---------------------------------------------------------------------------

# gpt2-large.chat-r80 (benchmark/workloads): 36 layers, 20 heads x 64, a
# pool of 257 blocks of 128 tokens, 32 slots of 1024, prefill bucket 512
LARGE = dict(layers=36, heads=20, head_dim=64, vocab=50257, positions=1024,
             blocks=257, block=128, slots=32, prefill=512, verify_q=5)
HALF_GB = 0.5e9


def _paged_step(kind, weights, sharding):
    """``(fn, args, donated argument, pool bytes, weight bytes in bf16)``
    for one program over a donated gpt2-large ``PagedKVCache``."""
    from apex_tpu.models import GPTConfig, GPTModel
    from apex_tpu.serving.cache import PagedKVCache
    g = LARGE
    model = GPTModel(GPTConfig(
        vocab_size=g["vocab"], hidden_size=g["heads"] * g["head_dim"],
        num_layers=g["layers"], num_attention_heads=g["heads"],
        max_position_embeddings=g["positions"]))

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    # "f32": the tree as the model stores it; "bf16": every leaf of it
    # bf16; "image": what the engines hand their programs, the model's
    # own image of the float32 tree (GPTModel.serving_params)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if weights == "image":
        params = jax.eval_shape(model.serving_params, params)
    elif weights == "bf16":
        params = jax.eval_shape(lambda: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, BF16), params))
    params = described(params)
    cache = described(jax.eval_shape(lambda: PagedKVCache.create(
        g["layers"], g["blocks"], g["heads"], g["block"], g["head_dim"])))
    n_weights = sum(x.size for x in jax.tree_util.tree_leaves(params))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=sharding)

    S, per_slot = g["slots"], g["positions"] // g["block"]
    if kind == "decode":
        def fn(params, cache, tokens, tables, lengths, ids, offs, src, dst):
            return model.forward(
                params, tokens[:, None], kv_cache=cache,
                block_tables=tables, lengths=lengths, append_block_ids=ids,
                append_offsets=offs, cow_src=src, cow_dst=dst)
        args = (params, cache, i32(S), i32(S, per_slot), i32(S), i32(S),
                i32(S), i32(S), i32(S))
    elif kind == "verify":
        Q = g["verify_q"]

        def fn(params, cache, tokens, tables, lengths, ids, offs, src, dst):
            return model.verify_forward(
                params, tokens, cache, block_tables=tables, lengths=lengths,
                append_block_ids=ids, append_offsets=offs, cow_src=src,
                cow_dst=dst)
        args = (params, cache, i32(S, Q), i32(S, per_slot), i32(S),
                i32(S, Q), i32(S, Q), i32(S), i32(S))
    elif kind == "prefill":
        def fn(params, cache, tokens, block_row, prompt_len):
            return model.forward(
                params, tokens, kv_cache=cache, block_row=block_row,
                prompt_len=prompt_len, last_logit_only=True)
        args = (params, cache, i32(1, g["prefill"]),
                i32(g["prefill"] // g["block"]), i32())
    else:
        def fn(cache, src, dst):
            return cache.cow_copy(src, dst)
        args = (cache, i32(S), i32(S))
    return fn, args, args.index(cache), cache.nbytes(), 2 * n_weights


_ARRAY_RESULT = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\]\S* "
                           r"([\w\-]+)\(")
# a result of the pool's size may only be the pool itself, passed on or
# written where it lies
_IN_PLACE = {"parameter", "get-tuple-element", "bitcast",
             "dynamic-update-slice", "scatter"}


# ... and one of a weight's size only the weight itself, passed on
_PASSED_ON = {"parameter", "get-tuple-element", "bitcast"}
_CALLEE = re.compile(r"calls=%?([\w.\-]+)")


def _weight_sizes():
    """Elements of each stacked matrix of gpt2-large's layers and of its
    word table: what a cast or a copy of a weight would have."""
    g = LARGE
    h = g["heads"] * g["head_dim"]
    return {g["layers"] * h * n for n in (h, 3 * h, 4 * h)} \
        | {g["vocab"] * h}


def _array_results(text):
    """``(computation, operation, elements, line, is root)`` of every
    instruction of compiled ``text`` with an array result."""
    current = None
    for line in text.splitlines():
        if line.rstrip().endswith("{") and "=" not in line.split("(")[0]:
            current = line.split("(")[0].replace("ENTRY", "").strip(" %")
        m = _ARRAY_RESULT.match(line)
        if m:
            count = 1
            for dim in m.group(3).split(","):
                count *= int(dim)
            yield current, m.group(4), count, line.strip(), bool(m.group(1))


def _pool_sized_copies(text, sizes):
    """Instructions of compiled ``text`` whose array result has one of
    ``sizes`` elements and is neither the pool passed on nor an in-place
    write of it (a fusion counts as what its root is)."""
    results = list(_array_results(text))
    roots = {comp: op for comp, op, _, _, root in results if root}
    bad = []
    for _, op, count, line, _ in results:
        if count not in sizes:
            continue
        if op == "fusion":
            op = roots.get(_CALLEE.search(line).group(1))
        if op not in _IN_PLACE:
            bad.append(line[:200])
    return bad


def _weight_sized_results(text, sizes):
    """Instructions of compiled ``text`` that WRITE an array of one of
    ``sizes`` elements: not the weight passed on, and not what stands
    inside a fusion (only the fusion's result reaches memory; it counts
    as passed on where everything inside it is)."""
    results = list(_array_results(text))
    inside = {}
    for comp, op, _, _, _ in results:
        inside.setdefault(comp, set()).add(op)
    fused = {_CALLEE.search(line).group(1)
             for _, op, _, line, _ in results if op == "fusion"}
    bad = []
    for comp, op, count, line, _ in results:
        if count not in sizes or comp in fused:
            continue
        ops = inside[_CALLEE.search(line).group(1)] if op == "fusion" \
            else {op}
        if not ops <= _PASSED_ON:
            bad.append(line[:200])
    return bad


@pytest.mark.parametrize("kind,weights", [
    ("decode", "bf16"), ("decode", "f32"), ("verify", "bf16"),
    ("prefill", "bf16"), ("cow_copy", "bf16"), ("decode", "image"),
    ("prefill", "image"), ("verify", "image")])
def test_paged_step_updates_the_pool_in_place_on_v5e(kind, weights,
                                                     one_chip, for_tpu):
    """The serve cell's programs keep no second image of the pool: no
    temporary of its size (3 GB an array), none of a layer's slice of it
    (84 MB), the donated pool aliased to the result. Handed the MODEL's
    float32 tree, XLA hoists its bf16 image (1.55 GB) out of the layer
    loop and makes it anew every run: allowed for here by its size, as
    what the model alone compiles to. Handed the ``image`` the engines
    make of that tree once (``GPTModel.serving_params``), a program
    casts and copies no weight: no result of a stacked matrix's or the
    word table's size but the weight itself, passed on."""
    fn, args, donated, pool_bytes, weight_image = _paged_step(
        kind, weights, one_chip)
    compiled = jax.jit(fn, donate_argnums=(donated,)).lower(*args).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert ("tpu_custom_call" in text) == (kind != "cow_copy")
    assert memory.alias_size_in_bytes >= pool_bytes
    budget = HALF_GB + (weight_image if weights == "f32" else 0)
    assert memory.temp_size_in_bytes < budget, memory.temp_size_in_bytes
    pool_elements = pool_bytes // 2 // 2           # K or V, bf16
    assert _pool_sized_copies(text, {
        pool_elements, pool_elements // LARGE["layers"]}) == []
    if weights == "image":
        assert _weight_sized_results(text, _weight_sizes()) == []


def test_the_decode_step_builds_the_walk_once_outside_the_layer_loop(
        one_chip, for_tpu):
    """The paged kernel's work list is every layer's: its ops (scope
    ``paged_work_list``) stand in the step's ENTRY computation, which runs
    once a step, or in fusions called from there, and nowhere else; the
    kernel is called from another computation, the layer loop's body."""
    fn, args, donated, _, _ = _paged_step("decode", "image", one_chip)
    text = jax.jit(fn, donate_argnums=(donated,)).lower(*args) \
        .compile().as_text()
    lines_of, entry, current = {}, None, None
    for line in text.splitlines():
        if line.rstrip().endswith("{") and "=" not in line.split("(")[0]:
            current = line.split("(")[0].replace("ENTRY", "").strip(" %")
            if line.startswith("ENTRY"):
                entry = current
        lines_of.setdefault(current, []).append(line)
    loop, = [c for c, ls in lines_of.items()
             if any("paged_decode_attention" in line and "custom-call(" in line
                    for line in ls)]
    assert loop != entry
    walk = {c for c, ls in lines_of.items()
            if any("/paged_work_list/" in line for line in ls)}
    fused = {name for line in lines_of[entry]
             for name in _CALLEE.findall(line)}
    assert entry in walk and walk <= {entry} | fused, walk - fused


# ---------------------------------------------------------------------------
# the pattern decoder's programs at command-a-plus-05-2026's published widths
# ---------------------------------------------------------------------------

# command-a-plus-05-2026.rag-r80 (benchmark/workloads): one chip of eight's
# share, 4 layers (3 window + 1 full), 16 query heads on 1 KV head of 128,
# 16 held + 4 shared experts of 4096, pools by kind for 32 slots of 8,704
COMMAND = dict(slots=32, max_len=8704, block=128, window=4096,
               blocks={"sliding_attention": 1057, "full_attention": 2177})


def _pattern_step(kind, sharding):
    from apex_tpu.models.pattern_decoder import (PatternDecoder,
                                                 PatternDecoderConfig)
    from apex_tpu.serving.cache import KindPagedKVCache
    c = COMMAND
    cfg = PatternDecoderConfig(
        vocab_size=32768, hidden_size=4096,
        layer_types=("sliding_attention",) * 3 + ("full_attention",),
        num_attention_heads=16, num_key_value_heads=1, head_dim=128,
        expert_size=4096, num_experts=128, num_experts_per_tok=8,
        held_experts=tuple(range(16)), num_shared_experts=4,
        sliding_window=c["window"], rope_theta=50000.0,
        max_position_embeddings=200000)
    model = PatternDecoder(cfg)

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    params = described(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = described(jax.eval_shape(lambda: KindPagedKVCache.create(
        cfg.cache_kinds, c["blocks"], 1, c["block"], 128)))
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=sharding)

    by_kind = lambda x: {k: x for k in cfg.cache_kinds}
    S, per_slot = c["slots"], c["max_len"] // c["block"]
    if kind == "decode":
        def fn(params, cache, tokens, tables, lengths, ids, offs):
            return model.forward(
                params, tokens[:, None], kv_cache=cache,
                block_tables=tables, lengths=lengths, append_block_ids=ids,
                append_offsets=offs)
        args = (params, cache, i32(S), by_kind(i32(S, per_slot)), i32(S),
                by_kind(i32(S)), i32(S))
    else:
        bucket = int(kind[len("prefill"):])

        def fn(params, cache, tokens, block_row, prompt_len):
            return model.forward(
                params, tokens, kv_cache=cache, block_row=block_row,
                prompt_len=prompt_len, last_logit_only=True)
        args = (params, cache, i32(1, bucket),
                by_kind(i32(bucket // c["block"])), i32())
    return fn, args, cache, weight_bytes


@pytest.mark.parametrize("kind", ["decode", "prefill1024", "prefill8192"])
def test_pattern_decoder_programs_compile_for_v5e(kind, one_chip, for_tpu,
                                                  monkeypatch):
    """The window kernels, the grouped-KV paged kernel and the expert
    product pass Mosaic at published widths; the pools are updated where
    they lie; weights + pools + temporaries fit the chip's 16 GB; and the
    stacked expert weights are read in place, not sliced a layer (a copy
    of one kind's gate matrices alone would be 2 GB)."""
    ep = importlib.import_module("apex_tpu.transformer.expert_parallel")
    monkeypatch.setattr(ep, "_interp", lambda: False)
    fn, args, cache, weight_bytes = _pattern_step(kind, one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert "tpu_custom_call" in text
    for name in ("moe_experts_gate_up", "moe_experts_down",
                 "paged_decode_attention" if kind == "decode"
                 else "flash_attention_window"):
        assert name in text, name
    pool_bytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree_util.tree_leaves(cache))
    assert memory.alias_size_in_bytes >= pool_bytes
    assert 8.4e9 < weight_bytes < 8.6e9
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert total < 15.5e9, (total, memory.temp_size_in_bytes)
    # a decode step and a short prefill keep their temporaries far under
    # one layer kind's expert stack
    if kind != "prefill8192":
        assert memory.temp_size_in_bytes < 1.0e9, memory.temp_size_in_bytes


# ---------------------------------------------------------------------------
# nemotron-3-super-120b-a12b.reason-r80 (benchmark/workloads): one chip of
# four's share, 11 layers MEMEMEM*EME, 32 Mamba heads of 64 in 2 groups
# with a state of 128, 8 query heads on 1 KV head of 128, 128 held of 512
# experts (1024 -> 2688 -> 1024) top-22, a shared expert of 5376; 64 slots
# of 4,096: a pool for the one attention layer, a state row a slot
# ---------------------------------------------------------------------------

NEMOTRON = dict(slots=64, max_len=4096, block=128,
                blocks={"attention": 64 * 32 + 1})
mamba2 = importlib.import_module("apex_tpu.ops.mamba2")


def _nemotron_model():
    from benchmark import run as harness
    from benchmark.families import nemotron_h
    cfg = harness.load_json(harness.HERE, "configs",
                            "nemotron-3-super-120b-a12b.json")
    return nemotron_h.model(cfg)


def _nemotron_step(kind, sharding):
    from apex_tpu.serving.cache import KindPagedKVCache
    c = NEMOTRON
    model = _nemotron_model()
    cfg = model.cfg

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    params = described(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = described(jax.eval_shape(lambda: KindPagedKVCache.create(
        cfg.cache_kinds, c["blocks"], 1, c["block"], 128,
        max_seqs=c["slots"])))
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=sharding)

    by_kind = lambda x: {"attention": x}
    S, per_slot = c["slots"], c["max_len"] // c["block"]
    if kind == "decode":
        def fn(params, cache, tokens, tables, lengths, ids, offs):
            return model.forward(
                params, tokens[:, None], kv_cache=cache,
                block_tables=tables, lengths=lengths, append_block_ids=ids,
                append_offsets=offs)
        args = (params, cache, i32(S), by_kind(i32(S, per_slot)), i32(S),
                by_kind(i32(S)), i32(S))
    else:
        bucket = int(kind[len("prefill"):])

        def fn(params, cache, tokens, block_row, prompt_len, slot):
            return model.forward(
                params, tokens, kv_cache=cache, block_row=block_row,
                prompt_len=prompt_len, last_logit_only=True, slot=slot)
        args = (params, cache, i32(1, bucket),
                by_kind(i32(bucket // c["block"])), i32(), i32())
    return fn, args, cache, weight_bytes


@pytest.mark.parametrize("kind", ["decode", "prefill256", "prefill512",
                                  "prefill1024", "prefill2048"])
def test_nemotron_h_programs_compile_for_v5e(kind, one_chip, for_tpu,
                                             monkeypatch):
    """The cell's decode program and its four prefill programs at published
    widths: the scan kernel, the one-up-matrix expert kernels, the
    grouped-KV kernels pass Mosaic; the pool AND the per-slot state are
    updated where they lie (a decode step copies no whole state: PR 27's
    lesson, here 0.34 GB); weights 8.4 GB + caches + temporaries fit the
    chip."""
    ep = importlib.import_module("apex_tpu.transformer.expert_parallel")
    monkeypatch.setattr(ep, "_interp", lambda: False)
    monkeypatch.setattr(mamba2, "_interp", lambda: False)
    fn, args, cache, weight_bytes = _nemotron_step(kind, one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    names = ["moe_experts_up", "moe_experts_down",
             "paged_decode_attention" if kind == "decode"
             else "mamba2_chunk_scan"]
    for name in names:
        assert name in text, name
    assert "moe_experts_gate_up" not in text
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    state = cache.pools["mamba"].ssm
    assert state.shape == (5, 64, 32, 64, 128) and state.dtype == F32
    assert 0.45e9 < cache_bytes < 0.50e9
    assert memory.alias_size_in_bytes >= cache_bytes
    assert 8.3e9 < weight_bytes < 8.5e9, weight_bytes
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert total < 15.5e9, (total, memory.temp_size_in_bytes)
    # no copy of the state, of a layer's expert stack or of the shared
    # expert: the temporaries stay far under any of them but in the widest
    # prefill, whose worst-case expert rows are 0.6 GB
    limit = 1.2e9 if kind == "prefill2048" else 0.45e9
    assert memory.temp_size_in_bytes < limit, memory.temp_size_in_bytes


def test_the_scan_kernel_compiles_for_v5e_at_published_widths(one_chip,
                                                              for_tpu,
                                                              monkeypatch):
    monkeypatch.setattr(mamba2, "_interp", lambda: False)

    def fn(x, dt, A, B, C, n):
        return mamba2.mamba2_chunk_scan(x, dt, A, B, C, chunk=128, length=n,
                                        use_pallas=True)

    shapes = [((2048, 32, 64), BF16), ((2048, 32), F32), ((32,), F32),
              ((2048, 2, 128), BF16), ((2048, 2, 128), BF16), ((), I32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and "mamba2_chunk_scan" in calls[0], calls


# ---------------------------------------------------------------------------
# jamba2-3b.chat-r80 (benchmark/workloads): the whole model, 28 layers (26
# Mamba-1 of 5,120 channels with a state of 16, 2 attention of 20 query
# heads on 1 KV head of 128), each followed by a dense MLP of 8,192; 128
# slots of 1,280: a pool for the two attention layers, a state row a slot
# ---------------------------------------------------------------------------

JAMBA = dict(slots=128, max_len=1280, block=128,
             blocks={"attention_mlp": 1281})
mamba1 = importlib.import_module("apex_tpu.ops.mamba1")


def _jamba_step(kind, sharding):
    from apex_tpu.serving.cache import KindPagedKVCache
    from benchmark import run as harness
    from benchmark.families import jamba
    c = JAMBA
    model = jamba.model(harness.load_json(harness.HERE, "configs",
                                          "jamba2-3b.json"))
    cfg = model.cfg

    def described(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=sharding), tree)

    params = described(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = described(jax.eval_shape(lambda: KindPagedKVCache.create(
        cfg.cache_kinds, c["blocks"], 1, c["block"], 128,
        max_seqs=c["slots"])))
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree_util.tree_leaves(params))

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, I32, sharding=sharding)

    by_kind = lambda x: {"attention_mlp": x}
    S, per_slot = c["slots"], c["max_len"] // c["block"]
    if kind == "decode":
        def fn(params, cache, tokens, tables, lengths, ids, offs):
            return model.forward(
                params, tokens[:, None], kv_cache=cache,
                block_tables=tables, lengths=lengths, append_block_ids=ids,
                append_offsets=offs)
        args = (params, cache, i32(S), by_kind(i32(S, per_slot)), i32(S),
                by_kind(i32(S)), i32(S))
    else:
        bucket = int(kind[len("prefill"):])

        def fn(params, cache, tokens, block_row, prompt_len, slot):
            return model.forward(
                params, tokens, kv_cache=cache, block_row=block_row,
                prompt_len=prompt_len, last_logit_only=True, slot=slot)
        args = (params, cache, i32(1, bucket),
                by_kind(i32(bucket // c["block"])), i32(), i32())
    return fn, args, cache, weight_bytes


@pytest.mark.parametrize("kind", ["decode", "prefill128", "prefill256",
                                  "prefill512", "prefill1024"])
def test_jamba_programs_compile_for_v5e(kind, one_chip, for_tpu,
                                        monkeypatch):
    """The cell's decode program and its four prefill programs at published
    widths: the selective-scan kernel and the grouped-KV kernels (20 query
    heads on 1 KV head) pass Mosaic; the pool AND the per-slot state, (26,
    128, 16, 5120) float32 with no padded lanes, are updated where they lie
    (a decode step copies no whole state, 1.09 GB, and no whole conv tail:
    the kernel ``mamba1_decode_update`` has the state aliased in and out);
    no dense MLP's weights
    are copied out of their stack; a prefill holds no (T, 16, 5120) array;
    weights 6.06 GB + caches + temporaries fit the chip."""
    monkeypatch.setattr(mamba1, "_interp", lambda: False)
    fn, args, cache, weight_bytes = _jamba_step(kind, one_chip)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text, memory = compiled.as_text(), compiled.memory_analysis()
    for kernel in (("paged_decode_attention", "mamba1_decode_update")
                   if kind == "decode" else ("mamba1_selective_scan",)):
        assert kernel in text, kernel
    state = cache.pools["mamba_mlp"].ssm
    assert state.shape == (26, 128, 16, 5120) and state.dtype == F32
    assert cache.pools["mamba_mlp"].conv.shape == (26, 3, 128, 5120)
    cache_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(cache))
    assert 1.35e9 < cache_bytes < 1.37e9, cache_bytes   # 1.09 + 0.10 + 0.17
    assert memory.alias_size_in_bytes >= cache_bytes
    assert weight_bytes == 2 * 3029337472
    total = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
             + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    assert total < 15.5e9, (total, memory.temp_size_in_bytes)
    # no copy of the state (1.09 GB), of the conv tails (0.10 GB) or of a
    # kind's MLP stack (3.3 GB): a decode step's temporaries are its rows
    limit = 0.05e9 if kind == "decode" else 0.6e9
    assert memory.temp_size_in_bytes < limit, memory.temp_size_in_bytes
    if kind != "decode":
        entries = int(kind[len("prefill"):]) * 16 * 5120
        assert not [line[:160] for _, _, count, line, _
                    in _array_results(text)
                    if count == entries and re.search(r"= f32\[[\d,]*\b16,",
                                                      line)]


def test_the_selective_scan_kernel_compiles_for_v5e_at_published_widths(
        one_chip, for_tpu, monkeypatch):
    monkeypatch.setattr(mamba1, "_interp", lambda: False)

    def fn(u, delta, A, B, C, n):
        return mamba1.mamba1_selective_scan(u, delta, A, B, C, chunk=128,
                                            length=n, use_pallas=True)

    shapes = [((1024, 5120), BF16), ((1024, 5120), F32), ((16, 5120), F32),
              ((1024, 16), F32), ((1024, 16), F32), ((), I32)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and "mamba1_selective_scan" in calls[0], calls

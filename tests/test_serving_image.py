"""The serving engines' compute-dtype image of the weights
(docs/SERVING.md, "The weights an engine holds"): what
``GPTModel.serving_params`` makes of a float32 tree, that the cached
passes over it compute what they compute from the tree, and that both
engines make it once at build and at every swap, keep it and nothing of
what they were handed, and compile nothing for a swap."""

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from apex_tpu.analysis.program import recompile_guard
from apex_tpu.models import GPTConfig, GPTModel
from apex_tpu.observability import trace
from apex_tpu.observability.registry import MetricsRegistry
from apex_tpu.serving import (CheckpointWatcher, PagedServingEngine,
                              Request, ServingEngine, SlotScheduler)
from apex_tpu.serving.cache import PagedKVCache

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def model_params():
    """float32 stored, bfloat16 compute: the configuration the serve
    cells run."""
    model = GPTModel(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                               num_attention_heads=4,
                               max_position_embeddings=64))
    return model, model.init(jax.random.PRNGKey(0))


def default_pool(model, params, **kw):
    return ServingEngine(model, params, max_seqs=2, max_len=32,
                         prefill_len=8, **kw)


def paged(model, params, **kw):
    return PagedServingEngine(model, params, max_seqs=2, max_len=32,
                              prefill_len=8, num_blocks=17, block_size=4,
                              **kw)


ENGINES = pytest.mark.parametrize("build", [default_pool, paged])


MATRICES = ("qkv", "proj", "fc1", "fc2")


def cast_at_use(params):
    """``params`` with the layers' matrices rounded by hand to the dtype
    the products take them in; the tables left for the float32 lookup
    (the tied head rounds its operand itself), biases and norms as
    stored (the passes cast them where they use them). The trainer's
    structure: no head leaf, the tensor axis kept."""
    layers = {k: dict(v) for k, v in params["layers"].items()}
    for name in MATRICES:
        layers[name]["weight"] = layers[name]["weight"].astype(BF16)
    return dict(params, layers=layers)


def matrices(tree):
    return [tree["layers"][name]["weight"] for name in MATRICES] \
        + [tree["head"]["weight"]]


def serve(engine, prompts=((1, 2, 3), (4, 5, 6, 7, 8), (9,)), new=6):
    reg = MetricsRegistry()
    done = SlotScheduler(engine, registry=reg).run(
        [Request(prompt=list(p), max_new_tokens=new, request_id=i)
         for i, p in enumerate(prompts)])
    return [list(done[i].tokens) for i in range(len(prompts))], \
        reg.snapshot()


def dtypes(tree):
    return {jax.tree_util.keystr(path): leaf.dtype for path, leaf in
            jax.tree_util.tree_leaves_with_path(tree)}


def test_the_image_has_the_dtypes_of_use(model_params):
    model, params = model_params
    image = model.serving_params(params)
    kinds = dtypes(image)
    assert all(m.dtype == BF16 for m in matrices(image))
    # all else as stored: the lookup tables, biases, norms
    assert sum(d == BF16 for d in kinds.values()) == len(MATRICES) + 1
    assert len(kinds) == len(dtypes(params)) + 1          # the head's copy
    assert kinds["['embedding']['position']"] == F32
    # both copies of the word table in the shape the gather takes
    assert image["embedding"]["word"]["weight"].shape == (97, 32)
    assert image["head"]["weight"].shape == (97, 32)
    np.testing.assert_array_equal(
        np.asarray(image["embedding"]["word"]["weight"]),
        np.asarray(params["embedding"]["word"]["weight"][0]))
    # an image is its own image, and so is a tree stored as it is used
    assert dtypes(model.serving_params(image)) == kinds
    as_used = jax.tree_util.tree_map(lambda x: x.astype(F32), params)
    f32_model = GPTModel(GPTConfig(
        vocab_size=97, hidden_size=32, num_layers=2, num_attention_heads=4,
        max_position_embeddings=64, compute_dtype=F32))
    same = f32_model.serving_params(as_used)
    assert jax.tree_util.tree_structure(same) == \
        jax.tree_util.tree_structure(params)


@pytest.mark.parametrize("leg", ["plain", "prefill", "decode", "verify"])
def test_forward_over_the_image_is_forward_over_the_hand_cast_tree(
        model_params, leg):
    """Bit for bit: the image changes where the rounding happens, not
    what is computed. (On the CPU the mixed product keeps a float32
    operand unrounded, so the float32 tree itself is no yardstick here;
    the chip rounds it, which is what the image stores.)"""
    model, params = model_params
    image, by_hand = model.serving_params(params), cast_at_use(params)
    tokens = jnp.asarray([[5, 9, 2, 77, 31, 8]], jnp.int32)

    def run(p):
        if leg == "plain":
            return model.forward(p, tokens)
        # two slots of 16 positions in blocks of 2, slot 1 holds the prompt
        cache = PagedKVCache.create(2, 17, 4, 2, 8)
        tables = jnp.arange(1, 17, dtype=jnp.int32).reshape(2, 8)
        logits, cache = model.forward(p, tokens, kv_cache=cache,
                                      block_row=tables[1, :3], prompt_len=6)
        if leg == "prefill":
            return logits
        lengths = jnp.asarray([0, 6], jnp.int32)
        at = dict(kv_cache=cache, block_tables=tables, lengths=lengths)
        if leg == "decode":
            return model.forward(
                p, jnp.asarray([[3], [4]], jnp.int32),
                append_block_ids=jnp.asarray([1, 12], jnp.int32),
                append_offsets=jnp.zeros(2, jnp.int32), **at)[0]
        return model.verify_forward(
            p, jnp.asarray([[3, 1, 2], [4, 5, 6]], jnp.int32),
            append_block_ids=jnp.asarray([[1, 1, 2], [12, 12, 13]],
                                         jnp.int32),
            append_offsets=jnp.asarray([[0, 1, 0]] * 2, jnp.int32),
            **at)[0]

    got, want = jax.jit(run)(image), jax.jit(run)(by_hand)
    assert got.dtype == want.dtype == F32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@ENGINES
def test_an_engine_holds_the_image_and_nothing_it_was_handed(
        model_params, build):
    model, params = model_params
    handed = jax.tree_util.tree_map(jnp.array, params)    # this test's own
    with trace.span_recording():
        engine = build(model, handed)
        built = {s.name for s in trace.drain_spans()}
    assert "compile.image" in built
    assert engine.params_spec == jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), params)
    held = jax.tree_util.tree_leaves(engine.params)
    theirs = {id(l) for l in jax.tree_util.tree_leaves(handed)}
    assert not theirs & {id(l) for l in held}
    # no float32 matrix but the two lookup tables
    assert all(m.dtype == BF16 for m in matrices(engine.params))
    assert sorted(l.shape for l in held
                  if l.dtype == F32 and l.ndim > 1
                  and min(l.shape[-2:]) > 2) == \
        [(64, 32), (97, 32)]
    assert engine.weights_handed_bytes == sum(
        l.nbytes for l in jax.tree_util.tree_leaves(params))
    assert engine.weights_held_bytes == sum(l.nbytes for l in held) \
        < engine.weights_handed_bytes
    tokens, gauges = serve(engine)
    assert gauges["serve/weights_handed_bytes"] == engine.weights_handed_bytes
    assert gauges["serve/weights_held_bytes"] == engine.weights_held_bytes
    # whoever deletes the leaves of engine.params has freed the weights
    # (benchmark/kinds/serve.py::Server.free), and none of the caller's
    for leaf in held:
        leaf.delete()
    assert all(l.is_deleted() for l in held)
    assert not any(l.is_deleted()
                   for l in jax.tree_util.tree_leaves(handed))
    assert serve(build(model, handed))[0] == tokens


@ENGINES
def test_a_swap_casts_with_the_program_build_compiled(model_params, build):
    """A swap takes the float32 tree build took, compiles nothing, and
    serves what an engine built on that tree serves."""
    model, params = model_params
    new_params = model.init(jax.random.PRNGKey(123))
    fresh, _ = serve(build(model, new_params))
    engine = build(model, params)
    before, _ = serve(engine)
    assert before != fresh
    serve(engine)                                   # host paths warm
    with recompile_guard("swap to a float32 tree"):
        engine.swap_params(new_params)
        after, _ = serve(engine)
    assert after == fresh
    assert engine.swaps == 1
    assert dtypes(engine.params) == dtypes(model.serving_params(params))


@ENGINES
def test_a_swap_refuses_another_structure_before_it_casts(model_params,
                                                          build):
    model, params = model_params
    engine = build(model, params)
    held = jax.tree_util.tree_leaves(engine.params)
    cast = engine._image_compiled
    engine._image_compiled = lambda tree: pytest.fail("cast before check")
    # the image is not what build took; nor is a tree of its dtypes
    for bad, why in ((model.serving_params(params), "structure"),
                     (cast_at_use(params), "never retrace"),
                     (jax.tree_util.tree_leaves(params), "structure")):
        with pytest.raises(ValueError, match=why):
            engine.swap_params(bad)
    engine._image_compiled = cast
    assert all(a is b for a, b in
               zip(held, jax.tree_util.tree_leaves(engine.params)))
    assert engine.swaps == 0


@ENGINES
def test_the_watcher_restores_into_the_handed_structure(model_params, build,
                                                        tmp_path):
    from apex_tpu.checkpoint import save_checkpoint
    model, params = model_params
    new_params = model.init(jax.random.PRNGKey(7))
    save_checkpoint(str(tmp_path), new_params, 3)     # the trainer's tree
    engine = build(model, params)
    watcher = CheckpointWatcher(engine, str(tmp_path),
                                registry=MetricsRegistry())
    assert watcher.poll() == 3
    want = model.serving_params(new_params)
    for got, ref in zip(jax.tree_util.tree_leaves(engine.params),
                        jax.tree_util.tree_leaves(want)):
        assert got.dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert serve(engine)[0] == serve(build(model, new_params))[0]


@ENGINES
def test_params_stored_as_used_are_held_as_handed(build):
    """Nothing to cast (float32 stored, float32 compute): no cast
    program, the very arrays, today's programs."""
    model = GPTModel(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                               num_attention_heads=4,
                               max_position_embeddings=64,
                               compute_dtype=F32))
    params = model.init(jax.random.PRNGKey(0))
    with trace.span_recording():
        engine = build(model, params)
        built = {s.name for s in trace.drain_spans()}
    assert "compile.image" not in built and "compile.decode" in built
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(engine.params),
        jax.tree_util.tree_leaves(params)))
    assert engine.weights_held_bytes == engine.weights_handed_bytes

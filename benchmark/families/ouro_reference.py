"""The plain reference of family ``ouro``: a LOOPED decoder, one stack of
sandwich-normed layers (rotary attention, then a dense gated MLP) run
``total_ut_steps`` times over its own output, in straightforward
``jax.numpy``, float32 under ``precision="highest"``. No kernel, no cache,
no batching, nothing imported from the program under test. It owns the
weights: ``make_weights`` draws every tensor from the seed, rounds it to
bfloat16 (the precision the configuration stores), and the family hands the
program the same arrays.

The equations (``x`` a row of ``hidden_size``; they follow ``transformers``'
``modeling_ouro.py``: ``OuroDecoderLayer``, ``OuroModel``; the
configuration's ``assumed`` lists each departure):

    rms(x; g) = x / sqrt(mean(x^2) + eps) * g                     float32
    x = E[tokens]
    for t in 1..total_ut_steps:           the SAME layers' weights each pass
      for l in 1..L:
        h = rms(x; g1_l)
        q, k, v = h Wq_l, h Wk_l, h Wv_l                 heads x d, no bias
        q, k = rope(q, p), rope(k, p)     p the token's own position
        a = softmax(q k^T / sqrt(d), causal over THIS pass's keys) v
        x = x + rms(a Wo_l; g2_l)
        m = (silu(rms(x; g3_l) Wgate_l) * (rms(x; g3_l) Wup_l)) Wdown_l
        x = x + rms(m; g4_l)
      x = rms(x; g_final)                 after EVERY pass; the next reads it
    logits = x W_head                     untied, the last pass's normed x

    rope(x, p) = x cos(p inv) + rotate_half(x) sin(p inv),
        rotate_half([a, b]) = [-b, a] over the head's two halves,
        inv_j = theta^(-2j/d), j < d/2

The exit gate (``lambda_t = sigmoid(x w_exit + b_exit)`` after each pass) is
drawn and held and computed by neither side: at ``early_exit_threshold`` 1
every token runs every pass, so the gate decides nothing.

Layout (``n`` layers; linear weights ``(in, out)``; every tensor bfloat16):

    embedding (V, hidden)  lm_head (hidden, V)  final_norm (hidden,)
    exit_gate (hidden, 1)  exit_gate_bias (1,)
    layers["rope_attention_mlp"]: norm, mixer_out_norm, ff_norm, ff_out_norm
        (n, hidden)  wq (n, hidden, heads d)  wk, wv (n, hidden, kv d)
        wo (n, heads d, hidden)  mlp_gate, mlp_up (n, hidden, F)
        mlp_down (n, F, hidden)

At published widths the float32 image of the weights does not fit a chip
beside the activations, so a pass is a scan over the stacked layers that
converts one layer's tensors at a time, and ``ServeReference`` walks the
passes; every request is padded to ONE width, so a pass compiles once a
run.

``control``: ``"int8"`` / ``"fp8"`` round both operands of every linear
product (projections, MLP, head) to that grid, scaled by the tensor's
absmax: the precision below the bfloat16 the configuration states. The names
in ``FAULTS`` plant one fault of arithmetic each in the same place, so that
tests and calibration can show the comparison refuses them.
"""

import jax
import jax.numpy as jnp
import numpy as np

# what every family's reference shares, written once beside the first of
# them: the seed as data, the control's rounding, a linear product in
# float32 (rounded on both sides under a control), a result built a chunk
# of rows at a time, interleaved rotary positions (here a planted fault)
from benchmark.families.cohere2_moe_reference import (  # noqa: F401
    _linear, _over_rows, rotary, seed_key)

HI = "highest"
KIND = "rope_attention_mlp"
FAULTS = ("pass_left_out", "first_pass_cache", "pass_norm_left_out",
          "rotary_interleaved", "out_norms_left_out")
Q_CHUNK = 128          # query rows scored at a time (bounds the score tile)
GAINS = ("norm", "mixer_out_norm", "ff_norm", "ff_out_norm", "final_norm")

# THE INIT IS PART OF THE YARDSTICK (PERF.md section 2): every matrix, the
# exit gate and the embedding N(0, initializer_range) as the HF class draws
# them (initializer_range 0.02: the class's default, the published
# config.json has no such key); the pre-norm and final gains 1 + N(0,
# initializer_range) where the class sets them to 1, so that a fault in how
# a gain is applied shows. No residual projection is scaled down: every
# sub-block's output is normed before it joins the residual, so its scale
# is the gain of that norm, and those two gains are drawn OUT_GAIN x (1 +
# N(0, initializer_range)). At 1, each of a pass's 96 sub-blocks adds a
# unit vector to a state that starts the pass at unit RMS, and the random
# 192-layer stack is chaotic: bfloat16 rounding alone moved 60% of the
# picks (0.12 of a logit on a TPU v5e, PERF.md section 2), more than
# rotary pairs interleaved instead of halved move them, so the cell's limit
# could not see that fault. At 0.3 the rounding reads under 0.01 and every
# planted fault over 0.2 (PERF.md section 2 has the readings).
OUT_GAIN = 0.3
OUT_NORMS = ("mixer_out_norm", "ff_out_norm")


def passes(cfg):
    return int(cfg["total_ut_steps"])


def layer_types(cfg):
    """The program's layer kind for each published ``layer_types`` entry:
    every layer of the stack is full attention, here rotary attention then
    the MLP (no other entry is served)."""
    types = tuple(cfg["layer_types"])
    if len(types) != cfg["num_hidden_layers"] or set(types) != {
            "full_attention"}:
        raise ValueError(f"family ouro serves {cfg['num_hidden_layers']} "
                         f"full_attention layers, not {sorted(set(types))}")
    return (KIND,) * len(types)


def weight_shapes(cfg):
    H, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    d = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    per_layer = {"norm": (H,), "mixer_out_norm": (H,), "ff_norm": (H,),
                 "ff_out_norm": (H,), "wq": (H, q), "wk": (H, kv),
                 "wv": (H, kv), "wo": (q, H), "mlp_gate": (H, F),
                 "mlp_up": (H, F), "mlp_down": (F, H)}
    n = cfg["num_hidden_layers"]
    return {"embedding": (V, H), "lm_head": (H, V), "final_norm": (H,),
            "exit_gate": (H, 1), "exit_gate_bias": (1,),
            "layers": {KIND: {name: (n,) + shape
                              for name, shape in per_layer.items()}}}


def make_weights(cfg, lo, hi):
    """Every tensor from the seed, stored bfloat16, inside one traced
    function (call it under ``jax.jit``). Each stacked tensor is drawn one
    layer at a time, so that the float32 draws of a gigabyte-sized stack
    never exist at once."""
    std = cfg["initializer_range"]
    base = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        stacked = path[0].key == "layers"
        tail = shape[1:] if stacked else shape

        def draw(j, key=jax.random.fold_in(base, i), tail=tail, name=name):
            x = std * jax.random.normal(jax.random.fold_in(key, j), tail,
                                        jnp.float32)
            if name in GAINS:
                x = (OUT_GAIN if name in OUT_NORMS else 1.0) * (1.0 + x)
            return x.astype(jnp.bfloat16)

        out.append(jax.lax.map(draw, jnp.arange(shape[0])) if stacked
                   else draw(0))
    return jax.tree_util.tree_unflatten(treedef, out)


def norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain.astype(jnp.float32)


def rope(x, theta):
    """``x`` ``(T, heads, d)`` at positions 0..T-1, pairs ``(j, j +
    d/2)``: ``x cos + rotate_half(x) sin``."""
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-b, a], axis=-1) * sin


# -- one layer ----------------------------------------------------------------


def attention(cfg, lp, h, mode, live=None, kv=None):
    """Rotary attention over the rows of ``h``, causal; ``kv`` (fault
    ``first_pass_cache``): the keys and values to read in place of this
    pass's own. Returns the output projection and this pass's own ``(k,
    v)``."""
    T = h.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["head_dim"]
    rot = (lambda x: rotary(x, cfg["rope_theta"])) \
        if mode == "rotary_interleaved" \
        else (lambda x: rope(x, cfg["rope_theta"]))
    q = rot(_linear(h, lp["wq"], mode).reshape(T, nh, d))
    own = (rot(_linear(h, lp["wk"], mode).reshape(T, nkv, d)),
           _linear(h, lp["wv"], mode).reshape(T, nkv, d))
    k, v = (jnp.repeat(a, nh // nkv, axis=1) for a in (kv or own))
    col = jnp.arange(T)
    chunk = Q_CHUNK if T % Q_CHUNK == 0 else T     # a short one whole

    def rows(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, 0)
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HI) / np.sqrt(d)
        dead = col[None, :] > (start + jnp.arange(chunk))[:, None]
        p = jax.nn.softmax(jnp.where(dead[None], -jnp.inf, s), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          precision=HI).reshape(chunk, nh * d)

    ctx = _over_rows(rows, T, chunk, nh * d, live)
    return _linear(ctx, lp["wo"], mode), own


def mlp(lp, h, mode):
    mid = jax.nn.silu(_linear(h, lp["mlp_gate"], mode)) \
        * _linear(h, lp["mlp_up"], mode)
    return _linear(mid, lp["mlp_down"], mode)


def layer(cfg, lp, x, mode, live=None, kv=None):
    """One sandwich layer over the rows of ``x``; with ``live`` (a traced
    count) the rows after the first ``live`` are padding: no real row reads
    them. Returns ``x`` and the layer's own ``(k, v)``."""
    eps = cfg["rms_norm_eps"]
    out = (lambda y, g: y) if mode == "out_norms_left_out" \
        else (lambda y, g: norm(y, lp[g], eps))
    a, own = attention(cfg, lp, norm(x, lp["norm"], eps), mode, live, kv)
    x = x + out(a, "mixer_out_norm")
    m = mlp(lp, norm(x, lp["ff_norm"], eps), mode)
    return x + out(m, "ff_out_norm"), own


# -- the stack ----------------------------------------------------------------


def one_pass(cfg, layers, x, mode, live=None, kv=None, keep=False):
    """Every layer of the stack once over ``x``, one layer's weights at a
    time (a scan over the stacked tensors); ``kv`` (fault
    ``first_pass_cache``): the first pass's keys and values, stacked by
    layer, to read in place of this pass's own. Returns ``x`` and, with
    ``keep``, this pass's own keys and values stacked by layer."""
    def body(x, xs):
        lp, kv_l = xs
        x, own = layer(cfg, lp, x, mode, live, kv_l)
        return x, own if keep else None

    return jax.lax.scan(body, x, (layers, kv))


def stack(cfg, x, mode, pass_fn, norm_fn):
    """The passes: ``pass_fn(x, kv, keep) -> (x, own)`` runs every layer
    once, ``norm_fn(x)`` is the final norm, after every pass (fault
    ``pass_norm_left_out``: after the last alone). Fault ``pass_left_out``
    runs one pass fewer, ``first_pass_cache`` has every later pass read
    the first pass's keys and values of the same layer."""
    n = passes(cfg) - (mode == "pass_left_out")
    first = None
    for t in range(n):
        x, own = pass_fn(x, first, mode == "first_pass_cache" and t == 0)
        if t == 0:
            first = own
        if mode != "pass_norm_left_out" or t == n - 1:
            x = norm_fn(x)
    return x


def head(w, x, mode):
    """The untied head over the last pass's normed ``x``."""
    return _linear(x, w["lm_head"], mode)


def forward(cfg, w, tokens, mode=False):
    """Logits ``(T, V)`` of one sequence, every layer of every pass in turn
    (small sizes: tests)."""
    x = w["embedding"][tokens].astype(jnp.float32)
    x = stack(cfg, x, mode,
              lambda x, kv, keep: one_pass(cfg, w["layers"][KIND], x, mode,
                                           kv=kv, keep=keep),
              lambda x: norm(x, w["final_norm"], cfg["rms_norm_eps"]))
    return head(w, x, mode)


class ServeReference:
    """Teacher-forced logits over ``prompt + served tokens``, one request
    at a time, one pass and one layer at a time; what comes back is small:
    at every served position the gap of the served token below the best
    logit and, with ``control``, the gap of the token the control (a lower
    precision or a planted fault) puts first."""

    def __init__(self, cfg, width, control=False):
        self.cfg, self.control = cfg, control
        self.width = -(-width // Q_CHUNK) * Q_CHUNK

        def one(layers, x, live, kv, keep, mode):
            with jax.default_matmul_precision(HI):
                return one_pass(cfg, layers, x, mode, live, kv, keep)

        self._pass = {mode: jax.jit(
            lambda layers, x, live, kv, keep, mode=mode:
            one(layers, x, live, kv, keep, mode), static_argnums=4)
            for mode in {False, control}}
        self._embed = jax.jit(
            lambda emb, tokens: emb[tokens].astype(jnp.float32))
        self._norm = jax.jit(
            lambda x, g: norm(x, g, cfg["rms_norm_eps"]))

        def gaps(w, x, x_low, nxt):
            logits = head(w, x, False)
            best = jnp.max(logits, -1)
            served = jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
            if not control:
                return best - served, jnp.zeros_like(best)
            low = jnp.argmax(head(w, x_low, control), -1)
            at_low = jnp.take_along_axis(logits, low[:, None], -1)[:, 0]
            return best - served, best - at_low

        self._gaps = jax.jit(gaps)

    def _stack(self, w, tokens, live, mode):
        x = self._embed(w["embedding"], tokens)
        layers = w["layers"][KIND]
        return stack(self.cfg, x, mode,
                     lambda x, kv, keep: self._pass[mode](layers, x, live,
                                                          kv, keep),
                     lambda x: self._norm(x, w["final_norm"]))

    def gaps(self, w, prompts, streams):
        served, control = [], []
        top = {"lm_head": w["lm_head"]}
        for p, s in zip(prompts, streams):
            seq = list(p) + list(s)
            tokens = np.zeros((self.width,), np.int32)
            nxt = np.zeros((self.width,), np.int32)
            tokens[:len(seq)] = seq
            nxt[:len(seq) - 1] = seq[1:]
            live = np.int32(len(seq))
            x = self._stack(w, tokens, live, False)
            x_low = self._stack(w, tokens, live, self.control) \
                if self.control else x
            g_served, g_ctrl = (np.asarray(g) for g in
                                self._gaps(top, x, x_low, nxt))
            span = slice(len(p) - 1, len(p) - 1 + len(s))
            served.append(g_served[span])
            control.append(g_ctrl[span])
        return served, control

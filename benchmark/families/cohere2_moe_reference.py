"""The plain reference of family ``cohere2_moe``: a decoder of parallel
blocks (one bias-free LayerNorm feeding grouped-query attention and a
top-k sigmoid expert layer side by side) whose ``layer_types`` mix window
layers with rotary positions and full layers with none, in straightforward
``jax.numpy``, float32 under ``precision="highest"``. No kernel, no cache,
no batching, nothing imported from the program under test. It owns the
weights: ``make_weights`` draws every tensor from the seed, rounds it to
bfloat16 (the precision the configuration stores), and the family hands the
program the same arrays.

The equations (``h``, ``x`` rows of ``hidden_size``):

    n(x)  = (x - mean) * rsqrt(var + eps) * g                 no bias
    block   h = n(x);  x' = x + attn(h) + moe(h)
    attn    q = h Wq, k = h Wk, v = h Wv; query head i reads KV head
            i // (heads / kv_heads); scale 1/sqrt(head_dim);
            sliding_attention: rotary over the whole head, pairs (2j, 2j+1),
            theta rope_theta, and row i reads j with 0 <= i - j < window;
            full_attention: no positional term, causal
    moe     s = sigmoid(h Wr) over ALL router_width experts; the
            num_experts_per_tok largest; w = s_sel / sum(s_sel);
            E(h) = (silu(h Wg) * (h Wu)) Wd;
            moe(h) = sum_{sel and held} w_e E_e(h) + mean_i S_i(h)
    head    logits = logit_scale * n(x) Wemb^T                 tied

THE SHARE: the configuration is one chip's part of every layer
(``held_experts`` of the router's width, the chip's query heads and their
KV head, its rows of the embedding). A token's weights are normalised over
all of its picks and only the held picks add to the result; nothing stands
in for what the absent chips would add, and the partial ``x'`` goes on.

Layout (per layer kind ``k`` with ``n`` layers of it, in order of
appearance; linear weights ``(in, out)``):

    embedding (V, H)   final_norm (H,)
    layers[k]: norm (n, H)  wq (n, H, heads*d)  wk, wv (n, H, kv*d)
               wo (n, heads*d, H)  router (n, H, router_width)
               w_gate, w_up (n, held + shared, H, F)
               w_down (n, held + shared, F, H)    held experts first

At published widths the float32 image of the weights does not fit a chip
beside the activations (4.2 B parameters), so ``ServeReference`` walks the
layers and converts one layer's tensors at a time. Every request is padded
to ONE width, so a layer kind compiles once a run, and the two parts whose
work grows with the rows (the attention's scores, the experts) are computed
a chunk of rows at a time, for the chunks that hold a real row only.

``control``: ``"int8"`` / ``"fp8"`` round both operands of every linear
layer's products (projections, experts, tied head) to that grid, scaled by
the tensor's absmax — the precision below the bfloat16 the configuration
states. The names in ``FAULTS`` plant one fault of arithmetic each in the
same place, so that tests and calibration can show the comparison refuses
them.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = "highest"
FAULTS = ("window_ignored", "rotary_on_full", "shared_summed",
          "dropped_expert", "held_norm")
Q_CHUNK = 128          # query rows scored at a time (bounds the score tile)
ROW_CHUNK = 1024       # rows the experts take at a time
WIDTH_STEP = 1024      # the one width of a run is a multiple of this
WO_GAIN = 2.0          # Wo ~ N(0, WO_GAIN * std): see make_weights


def seed_key(seed):
    """Two 32-bit halves of any whole number up to 2**63, folded in as
    data so that one compiled program serves every seed."""
    seed = int(seed)
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def layer_types(cfg):
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def kinds(cfg):
    """Layer kinds in order of first appearance -> how many layers."""
    types = layer_types(cfg)
    return {k: types.count(k) for k in dict.fromkeys(types)}


def local_experts(cfg):
    return len(cfg["held_experts"]) + cfg["num_shared_experts"]


def weight_shapes(cfg):
    H, d, F = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    n = local_experts(cfg)
    per_layer = {"norm": (H,), "wq": (H, q), "wk": (H, kv), "wv": (H, kv),
                 "wo": (q, H), "router": (H, cfg["router_width"]),
                 "w_gate": (n, H, F), "w_up": (n, H, F), "w_down": (n, F, H)}
    return {"embedding": (cfg["vocab_size"], H), "final_norm": (H,),
            "layers": {k: {name: (count,) + shape
                           for name, shape in per_layer.items()}
                       for k, count in kinds(cfg).items()}}


def make_weights(cfg, lo, hi):
    """Every tensor from the seed, stored bfloat16, inside one traced
    function (call it under ``jax.jit``). Matrices ~ N(0, std); the
    experts' down projection N(0, std / sqrt(2 L)); norm gains 1 +
    N(0, std) (drawn, not 1, so that a fault in how they are applied
    shows). The attention's output projection is N(0, WO_GAIN * std): with
    random weights a softmax over thousands of positions averages its
    values down to a tenth of what one expert puts out, and at GPT-2's
    scaling of Wo a fault in the attention (a window ignored, rotary on
    the wrong kind of layer) moved no served token; at this gain both
    branches of the parallel block add about as much to the residual.
    Each tensor is drawn one leading-axis slice at a time, so that the
    float32 draws of a gigabyte-sized stack never exist at once."""
    std = cfg["initializer_range"]
    L = cfg["num_hidden_layers"]
    base = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        scale = {"wo": WO_GAIN * std,
                 "w_down": std / math.sqrt(2.0 * L)}.get(name, std)
        lead = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
        tail = shape[-2:] if len(shape) > 2 else shape

        def draw(j, key=jax.random.fold_in(base, i), tail=tail, scale=scale,
                 name=name):
            x = scale * jax.random.normal(jax.random.fold_in(key, j), tail,
                                          jnp.float32)
            if name in ("norm", "final_norm"):
                x = 1.0 + x
            return x.astype(jnp.bfloat16)

        if len(shape) > 2:
            x = jax.lax.map(draw, jnp.arange(lead)).reshape(shape)
        else:
            x = draw(0)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


def _round_to(x, mode):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    if mode == "int8":
        scale = amax / 127.0
        return jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale
    if mode == "fp8":
        scale = amax / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    raise ValueError(f"unknown control precision {mode!r}")


def _linear(x, w, quant):
    """``x @ w`` in float32; with ``quant`` both operands on that grid."""
    w = w.astype(jnp.float32)
    if quant in ("int8", "fp8"):
        x, w = _round_to(x, quant), _round_to(w, quant)
    return jnp.matmul(x, w, precision=HI)


def norm(x, gain, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def rotary(x, theta):
    """``x`` ``(T, heads, d)`` at positions 0..T-1, pairs interleaved."""
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                     a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _over_rows(block, total, chunk, width, live):
    """``block(start)`` gives rows ``start .. start + chunk`` of a ``(total,
    width)`` result; computed for the chunks that hold one of the first
    ``live`` rows (all of them when ``live`` is None). The rows after them
    stay 0: padding, which no real row reads (every layer is causal)."""
    n = total // chunk if live is None else (live + chunk - 1) // chunk

    def body(i, out):
        return jax.lax.dynamic_update_slice_in_dim(
            out, block(i * chunk), i * chunk, 0)

    return jax.lax.fori_loop(0, n, body,
                             jnp.zeros((total, width), jnp.float32))


def attention(cfg, lp, h, kind, mode, live=None):
    T = h.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = _linear(h, lp["wq"], mode).reshape(T, nh, d)
    k = _linear(h, lp["wk"], mode).reshape(T, nkv, d)
    v = _linear(h, lp["wv"], mode).reshape(T, nkv, d)
    sliding = kind == "sliding_attention"
    if sliding or mode == "rotary_on_full":
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(v, nh // nkv, axis=1)
    window = cfg["sliding_window"] \
        if sliding and mode != "window_ignored" else None
    col = jnp.arange(T)
    chunk = Q_CHUNK if T % Q_CHUNK == 0 else T     # a short one whole

    def rows(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, 0)
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HI) / math.sqrt(d)
        row = start + jnp.arange(chunk)
        dead = col[None, :] > row[:, None]
        if window is not None:
            dead = dead | (row[:, None] - col[None, :] >= window)
        p = jax.nn.softmax(jnp.where(dead[None], -jnp.inf, s), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          precision=HI).reshape(chunk, nh * d)

    ctx = _over_rows(rows, T, chunk, nh * d, live)
    return _linear(ctx, lp["wo"], mode)


def experts(cfg, lp, h, mode, live=None):
    """``expert_rows`` over ``h``, a chunk of rows at a time."""
    T, H = h.shape
    chunk = ROW_CHUNK if T % ROW_CHUNK == 0 else T
    return _over_rows(
        lambda start: expert_rows(
            cfg, lp, jax.lax.dynamic_slice_in_dim(h, start, chunk, 0), mode),
        T, chunk, H, live)


def expert_rows(cfg, lp, h, mode):
    """The held part of the routed sum and the mean of the shared
    experts: every local expert over every token, masked."""
    held = list(cfg["held_experts"])
    nh, ns, k = len(held), cfg["num_shared_experts"], \
        cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(h, lp["router"].astype(jnp.float32),
                                       precision=HI))
    top_s, top_e = jax.lax.top_k(scores, k)
    is_held = jnp.isin(top_e, jnp.asarray(held))
    if mode == "held_norm":        # fault: normalised over held picks only
        weight = top_s / jnp.maximum(
            jnp.sum(jnp.where(is_held, top_s, 0.0), -1, keepdims=True),
            1e-20)
    else:
        weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True)
    # (T, nh): the weight a token gives each held expert, 0 if not picked
    per = jnp.sum(jnp.where(top_e[..., None] == jnp.asarray(held),
                            weight[..., None], 0.0), axis=1)
    if mode == "dropped_expert":   # fault: one held expert never computed
        per = per.at[:, nh // 2].set(0.0)
    share = 1.0 if mode == "shared_summed" else 1.0 / ns
    per = jnp.concatenate([per, jnp.full((h.shape[0], ns), share)], axis=1)

    def one(acc, e):
        wg, wu, wd, pe = e
        mid = jax.nn.silu(_linear(h, wg, mode)) * _linear(h, wu, mode)
        return acc + pe[:, None] * _linear(mid, wd, mode), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], per.T))
    return out


def layer(cfg, lp, x, kind, mode, live=None):
    """One parallel block over the rows of ``x``; with ``live`` (a traced
    count) the rows after the first ``live`` are padding and come back
    without the block's two branches."""
    h = norm(x, lp["norm"], cfg["layer_norm_eps"])
    return (x + attention(cfg, lp, h, kind, mode, live)
            + experts(cfg, lp, h, mode, live))


def head(cfg, w, x, mode):
    h = norm(x, w["final_norm"], cfg["layer_norm_eps"])
    return cfg["logit_scale"] * _linear(h, w["embedding"].T, mode)


def forward(cfg, w, tokens, mode=False):
    """Logits ``(T, V)`` of one sequence, every layer in turn (small
    sizes: tests)."""
    x = w["embedding"][tokens].astype(jnp.float32)
    seen = dict.fromkeys(kinds(cfg), 0)
    for kind in layer_types(cfg):
        lp = {n: v[seen[kind]] for n, v in w["layers"][kind].items()}
        x = layer(cfg, lp, x, kind, mode)
        seen[kind] += 1
    return head(cfg, w, x, mode)


class ServeReference:
    """Teacher-forced logits over ``prompt + served tokens``, one request
    at a time and one layer at a time; what comes back is small: at every
    served position the gap of the served token below the best logit and,
    with ``control``, the gap of the token the control (a lower precision
    or a planted fault) puts first."""

    def __init__(self, cfg, width, control=False):
        self.cfg, self.control = cfg, control
        # one width a run, and nearly always the same one from run to run
        # (the longest request is always scored), so that a kind's layer
        # compiles once and a warm compile cache holds it
        step = WIDTH_STEP if width > WIDTH_STEP else Q_CHUNK
        self.width = -(-width // step) * step
        self._layer = {
            (kind, mode): jax.jit(
                lambda lp, x, live, kind=kind, mode=mode:
                layer(cfg, lp, x, kind, mode, live))
            for kind in kinds(cfg) for mode in {False, control}}
        self._embed = jax.jit(
            lambda emb, tokens: emb[tokens].astype(jnp.float32))

        def gaps(w, x, x_low, nxt):
            logits = head(cfg, w, x, False)
            best = jnp.max(logits, -1)
            served = jnp.take_along_axis(logits, nxt[:, None], -1)[:, 0]
            if not control:
                return best - served, jnp.zeros_like(best)
            low = jnp.argmax(head(cfg, w, x_low, control), -1)
            at_low = jnp.take_along_axis(logits, low[:, None], -1)[:, 0]
            return best - served, best - at_low

        self._gaps = jax.jit(gaps)

    def _stack(self, w, tokens, live, mode):
        cfg = self.cfg
        x = self._embed(w["embedding"], tokens)
        seen = dict.fromkeys(kinds(cfg), 0)
        for kind in layer_types(cfg):
            lp = {n: v[seen[kind]] for n, v in w["layers"][kind].items()}
            x = self._layer[kind, mode](lp, x, live)
            seen[kind] += 1
        return x

    def gaps(self, w, prompts, streams):
        served, control = [], []
        top = {k: w[k] for k in ("embedding", "final_norm")}
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

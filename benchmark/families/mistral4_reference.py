"""The plain reference of family ``mistral4`` (Mistral Small 4, DeepSeek-V3's
layer): a decoder whose every layer is latent attention (MLA) then a top-k
sigmoid expert layer with a selection bias and one shared expert, each
behind its own RMS norm; in straightforward ``jax.numpy``, float32 under
``precision="highest"``. No kernel, no cache, no batching, nothing imported
from the program under test. Attention is computed in the EXPANDED form
(every head's key and value made from the latent, one softmax a head over
all positions); the program's decode computes the absorbed form, which is
the same function. It owns the weights: ``make_weights`` draws every tensor
from the seed, rounds it to bfloat16 (the precision the configuration
stores), and the family hands the program the same arrays.

The equations (``x`` a row of ``hidden_size``; as published unless the
configuration's ``assumed`` says otherwise):

    n(x)  = x * rsqrt(mean(x^2) + eps) * g               RMS norm, float32
    layer   x = x + mla(n1(x));  x' = x + moe(n2(x))
    head    logits = n(x) Whead                           untied
    mla     c_q = n(x Wq_a);  q = c_q Wq_b -> heads x [q_nope | q_pe]
            [c | k_pe] = x Wkv_a;  c = n(c)               (k_pe ONE a row)
            q_pe, k_pe rotated by YaRN's table at their position, pairs
            (2i, 2i+1);  q = q * (1 + beta ln(1 + floor(p / original)))
            k_h = [c Wuk_h | k_pe];  v_h = c Wuv_h
            s_h(t) = q_h . k_h(t) * m^2 / sqrt(nope + rope), causal,
            m = 0.1 mscale_all_dim ln(factor) + 1
            mla = concat_h(softmax(s_h) v_h) Wo
    yarn    f_i = theta^(-2i/rope);  inv_i = f_i / factor * r_i + f_i (1 - r_i)
            r_i = clip((i - lo) / (hi - lo), 0, 1), [lo, hi] the dimensions
            that turn beta_fast .. beta_slow times over original positions
    moe     s = sigmoid(u Wr) over ALL router_width experts; the
            num_experts_per_tok largest of s + b;  w = s_pick / sum(s_pick)
            * routed_scaling_factor;  E(u) = (silu(u Wg) * (u Wu)) Wd
            moe = sum_{pick and held} w_e E_e(u) + S(u)   S the shared expert

THE SHARE: the configuration is one chip's part of every layer (its routed
experts of the router's width, its rows of the vocabulary); attention is
whole on every chip. A token's weights are normalised over all of its picks
and only the held picks add; nothing stands in for what the absent chips
would add, and the partial ``x'`` goes on. ``expert_parts`` gives the
feed-forward as (what the chips' shares sum to, what every chip computes
alike) for the test that adds the shares up.

Layout (the one layer kind ``mla_moe``, ``n`` layers; linear weights ``(in,
out)``; every tensor bfloat16):

    embedding (V, hidden)  lm_head (hidden, V)  final_norm (hidden,)
    layers["mla_moe"]: norm, ff_norm (n, hidden)  wq_a (n, hidden, q_lora)
        q_norm (n, q_lora)  wq_b (n, q_lora, heads (nope + rope))
        wkv_a (n, hidden, kv_lora + rope)  kv_norm (n, kv_lora)
        w_uk (n, kv_lora, heads nope)  w_uv (n, kv_lora, heads v)
        wo (n, heads v, hidden)  router (n, hidden, router_width)
        router_bias (n, router_width)  w_gate, w_up (n, held, hidden, F)
        w_down (n, held, F, hidden)  shared_gate, shared_up (n, hidden, F)
        shared_down (n, F, hidden)

At published widths the float32 image of the weights does not fit a chip
beside the activations, so ``ServeReference`` walks the layers and converts
one layer's tensors at a time; every request is padded to ONE width, so a
layer compiles once a run, and the attention's scores and the experts are
computed a chunk of rows at a time, for the chunks that hold a real row
only.

``control``: ``"int8"`` / ``"fp8"`` round both operands of every linear
product (projections, experts, head) to that grid, scaled by the tensor's
absmax: the precision below the bfloat16 the configuration states. The
names in ``FAULTS`` plant one fault of arithmetic each in the same place;
``k_pe_unrotated`` is the program's cache holding ``k_pe`` before its
rotation, so it shows from the first served position on (``seam``, the
prompt's length): every row there reads the earlier keys unrotated.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

# what every family's reference shares, written once beside the first of
# them: the seed as data, the control's rounding, a linear product in
# float32 (rounded on both sides under a control), a result built a chunk
# of rows at a time
from benchmark.families.cohere2_moe_reference import (  # noqa: F401
    _linear, _over_rows, _round_to, seed_key)

HI = "highest"
KIND = "mla_moe"
FAULTS = ("yarn_mscale_left_out", "llama4_scale_left_out", "rotary_halves",
          "k_pe_unrotated", "kv_norm_left_out", "held_norm")
Q_CHUNK = 128          # query rows scored at a time (bounds the score tile)
ROW_CHUNK = 1024       # rows the experts take at a time
WIDTH_STEP = 1024      # the one width of a run is a multiple of this

# THE INIT IS PART OF THE YARDSTICK: each sub-block has to add to the
# residual about as much as the others, or a fault in the small one moves no
# served token (PERF.md section 2). With std = initializer_range, a
# residual of rms 1 and these scales, one layer at published widths over a
# 4,096-token prompt adds (CPU, float32): attention rms 0.30 (0.15 at the
# last row), the shared expert 0.23, the routed sum 0.17.
WO_GAIN = 8.0          # attention's Wo ~ N(0, WO_GAIN * std): the softmax
#                        averages the values down over the context (its
#                        part falls as ~1/sqrt(positions)): at 4 x std it
#                        added rms 0.12 at the 1,024th row, a quarter of the
#                        shared expert
SHARED_GAIN = 0.25     # the shared expert's Wd ~ N(0, SHARED_GAIN * std):
#                        silu(g) u over 2,048 is rms ~0.9, so at 0.5 x std
#                        it added rms 0.46, every token, every layer
ROUTED_GAIN = 1.0      # a routed expert's Wd ~ N(0, ROUTED_GAIN * std): a
#                        token's held picks weigh ~1/8 of one expert (4 picks
#                        of 128 at weight ~1/4, 16 held), so at 0.5 x std the
#                        routed sum added rms 0.085
BIAS_STD = 0.01        # router_bias ~ N(0, router_bias_std or this): moves
#                        the choice near the 4th pick and not the load


def layer_types(cfg):
    return (KIND,) * cfg["num_hidden_layers"]


def kinds(cfg):
    return {KIND: cfg["num_hidden_layers"]}


def held_experts(cfg):
    """The ids of the routed experts held here (the configuration counts
    them: ``n_routed_experts`` from ``first_held_expert``, 0 when absent)."""
    first = cfg.get("first_held_expert", 0)
    return list(range(first, first + cfg["n_routed_experts"]))


def rope(cfg):
    """The rotary parameters, ``rope_parameters`` as published."""
    return cfg["rope_parameters"]


def weight_shapes(cfg):
    H, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, E, F = (cfg["n_routed_experts"], cfg["router_width"],
               cfg["moe_intermediate_size"])
    Fs = F * cfg["n_shared_experts"]
    per_layer = {
        "norm": (H,), "wq_a": (H, qr), "q_norm": (qr,),
        "wq_b": (qr, nh * (dn + dr)), "wkv_a": (H, kr + dr),
        "kv_norm": (kr,), "w_uk": (kr, nh * dn), "w_uv": (kr, nh * dv),
        "wo": (nh * dv, H), "ff_norm": (H,), "router": (H, E),
        "router_bias": (E,), "w_gate": (n, H, F), "w_up": (n, H, F),
        "w_down": (n, F, H), "shared_gate": (H, Fs), "shared_up": (H, Fs),
        "shared_down": (Fs, H)}
    L = cfg["num_hidden_layers"]
    return {"embedding": (cfg["vocab_size"], H),
            "lm_head": (H, cfg["vocab_size"]), "final_norm": (H,),
            "layers": {KIND: {name: (L,) + shape
                              for name, shape in per_layer.items()}}}


def _draw(name, key, shape, std, bias_std):
    normal = lambda s: s * jax.random.normal(key, shape, jnp.float32)
    if name in ("norm", "ff_norm", "final_norm", "q_norm", "kv_norm"):
        return 1.0 + normal(std)
    if name == "router_bias":
        return normal(bias_std)
    gain = {"wo": WO_GAIN, "w_down": ROUTED_GAIN,
            "shared_down": SHARED_GAIN}.get(name, 1.0)
    return normal(gain * std)


def make_weights(cfg, lo, hi):
    """Every tensor from the seed, stored bfloat16, inside one traced
    function (call it under ``jax.jit``); the scales are the constants
    above. Each tensor is drawn one leading-axis slice at a time, so that
    the float32 draws of a gigabyte-sized stack never exist at once."""
    std = cfg["initializer_range"]
    base = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        lead = int(np.prod(shape[:-2])) if len(shape) > 2 else 1
        tail = shape[-2:] if len(shape) > 2 else shape

        def draw(j, key=jax.random.fold_in(base, i), tail=tail, name=name):
            return _draw(name, jax.random.fold_in(key, j), tail, std,
                         cfg.get("router_bias_std", BIAS_STD)
                         ).astype(jnp.bfloat16)

        if len(shape) > 2:
            x = jax.lax.map(draw, jnp.arange(lead)).reshape(shape)
        else:
            x = draw(0)
        out.append(x)
    return jax.tree_util.tree_unflatten(treedef, out)


def norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain.astype(jnp.float32)


# -- positions ----------------------------------------------------------------


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def inv_freq(cfg):
    """YaRN's frequencies of the positional part (DeepSeek-V3's
    ``yarn_find_correction_range`` and linear ramp), float64 -> float32."""
    p, d = rope(cfg), cfg["qk_rope_head_dim"]
    base, factor = p["rope_theta"], p["factor"]
    orig = p["original_max_position_embeddings"]
    f = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    dim_of = lambda rot: (d * math.log(orig / (rot * 2 * math.pi))
                          / (2 * math.log(base)))
    lo = max(math.floor(dim_of(p["beta_fast"])), 0)
    hi = min(math.ceil(dim_of(p["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / max(hi - lo, 0.001), 0.0, 1.0)
    return (f / factor * ramp + f * (1.0 - ramp)).astype(np.float32)


def rotate(cfg, x, mode):
    """``x`` ``(T, heads, rope)`` at positions 0..T-1: pairs (2i, 2i+1), or
    under the ``rotary_halves`` fault pairs (i, i + rope/2)."""
    T, _, d = x.shape
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] \
        * jnp.asarray(inv_freq(cfg))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if mode == "rotary_halves":
        a, b = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def softmax_scale(cfg, mode):
    p = rope(cfg)
    m = yarn_mscale(p["factor"], p["mscale_all_dim"]) \
        if p["mscale_all_dim"] and mode != "yarn_mscale_left_out" else 1.0
    return m * m / math.sqrt(cfg["qk_nope_head_dim"]
                             + cfg["qk_rope_head_dim"])


# -- the two sub-blocks -------------------------------------------------------


def attention(cfg, lp, u, mode, live=None, seam=None):
    T = u.shape[0]
    nh, dn, dr, dv = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    kr, eps, p = cfg["kv_lora_rank"], cfg["rms_norm_eps"], rope(cfg)
    c_q = norm(_linear(u, lp["wq_a"], mode), lp["q_norm"], eps)
    q = _linear(c_q, lp["wq_b"], mode).reshape(T, nh, dn + dr)
    ckv = _linear(u, lp["wkv_a"], mode)
    c = ckv[:, :kr] if mode == "kv_norm_left_out" \
        else norm(ckv[:, :kr], lp["kv_norm"], eps)
    pe_raw = ckv[:, None, kr:]
    k_pe = rotate(cfg, pe_raw, mode)
    q = jnp.concatenate([q[..., :dn], rotate(cfg, q[..., dn:], mode)], -1)
    beta = p.get("llama_4_scaling_beta", 0.0)
    if beta and mode != "llama4_scale_left_out":
        steps = jnp.floor(jnp.arange(T, dtype=jnp.float32)
                          / p["original_max_position_embeddings"])
        q = q * (1.0 + beta * jnp.log1p(steps))[:, None, None]
    k_nope = _linear(c, lp["w_uk"], mode).reshape(T, nh, dn)
    v = _linear(c, lp["w_uv"], mode).reshape(T, nh, dv)
    key = lambda pe: jnp.concatenate(
        [k_nope, jnp.broadcast_to(pe, (T, nh, dr))], -1)
    k = key(k_pe)
    k_cached = key(pe_raw) if mode == "k_pe_unrotated" else None
    scale = softmax_scale(cfg, mode)
    col = jnp.arange(T)
    chunk = Q_CHUNK if T % Q_CHUNK == 0 else T     # a short one whole

    def rows(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, 0)
        row = start + jnp.arange(chunk)
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HI) * scale
        if k_cached is not None:
            # a row the program decodes reads the keys its cache holds
            s = jnp.where((row >= seam)[None, :, None], jnp.einsum(
                "qhd,khd->hqk", qc, k_cached, precision=HI) * scale, s)
        dead = col[None, :] > row[:, None]
        pr = jax.nn.softmax(jnp.where(dead[None], -jnp.inf, s), axis=-1)
        return jnp.einsum("hqk,khd->qhd", pr, v,
                          precision=HI).reshape(chunk, nh * dv)

    ctx = _over_rows(rows, T, chunk, nh * dv, live)
    return _linear(ctx, lp["wo"], mode)


def _expert(u, wg, wu, wd, mode):
    return _linear(jax.nn.silu(_linear(u, wg, mode)) * _linear(u, wu, mode),
                   wd, mode)


def expert_parts(cfg, lp, u, mode):
    """``(routed, shared)`` over the rows of ``u``: the held part of the
    routed sum (the chips' shares of it add up to the layer's) and the
    shared expert (every chip computes it alike)."""
    held = jnp.asarray(held_experts(cfg))
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(u, lp["router"].astype(jnp.float32),
                                       precision=HI))
    bias = lp["router_bias"].astype(jnp.float32)[None, :]
    _, top_e = jax.lax.top_k(scores + bias, k)
    top_s = jnp.take_along_axis(scores, top_e, -1)
    is_held = jnp.isin(top_e, held)
    if mode == "held_norm":        # fault: normalised over held picks only
        total = jnp.maximum(jnp.sum(jnp.where(is_held, top_s, 0.0), -1,
                                    keepdims=True), 1e-20)
    else:
        total = jnp.sum(top_s, axis=-1, keepdims=True)
    weight = top_s / total * cfg["routed_scaling_factor"]
    # (T, held): the weight a token gives each held expert, 0 if not picked
    per = jnp.sum(jnp.where(top_e[..., None] == held, weight[..., None],
                            0.0), axis=1)

    def one(acc, e):
        wg, wu, wd, pe = e
        return acc + pe[:, None] * _expert(u, wg, wu, wd, mode), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (lp["w_gate"], lp["w_up"], lp["w_down"], per.T))
    shared = _expert(u, lp["shared_gate"], lp["shared_up"],
                     lp["shared_down"], mode)
    return routed, shared


def experts(cfg, lp, u, mode, live=None):
    """The expert layer over ``u``, a chunk of rows at a time."""
    T, H = u.shape
    chunk = ROW_CHUNK if T % ROW_CHUNK == 0 else T
    return _over_rows(
        lambda start: sum(expert_parts(
            cfg, lp, jax.lax.dynamic_slice_in_dim(u, start, chunk, 0),
            mode)), T, chunk, H, live)


# -- the stack ----------------------------------------------------------------


def layer(cfg, lp, x, mode, live=None, seam=None):
    """One layer over the rows of ``x``: latent attention then the expert
    layer, each behind its own norm; with ``live`` (a traced count) the
    rows after the first ``live`` are padding, which no real row reads."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(cfg, lp, norm(x, lp["norm"], eps), mode, live, seam)
    return x + experts(cfg, lp, norm(x, lp["ff_norm"], eps), mode, live)


def head(cfg, w, x, mode):
    h = norm(x, w["final_norm"], cfg["rms_norm_eps"])
    return _linear(h, w["lm_head"], mode)


def forward(cfg, w, tokens, mode=False, seam=None):
    """Logits ``(T, V)`` of one sequence, every layer in turn (small
    sizes: tests)."""
    x = w["embedding"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        lp = {n: v[i] for n, v in w["layers"][KIND].items()}
        x = layer(cfg, lp, x, mode, seam=seam)
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
        # (the longest request is always scored)
        step = WIDTH_STEP if width > WIDTH_STEP else Q_CHUNK
        self.width = -(-width // step) * step
        self._layer = {
            mode: jax.jit(lambda lp, x, live, seam, mode=mode:
                          layer(cfg, lp, x, mode, live, seam))
            for mode in {False, control}}
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

    def _stack(self, w, tokens, live, seam, mode):
        x = self._embed(w["embedding"], tokens)
        for i in range(self.cfg["num_hidden_layers"]):
            lp = {n: v[i] for n, v in w["layers"][KIND].items()}
            x = self._layer[mode](lp, x, live, seam)
        return x

    def gaps(self, w, prompts, streams):
        served, control = [], []
        top = {k: w[k] for k in ("lm_head", "final_norm")}
        for p, s in zip(prompts, streams):
            seq = list(p) + list(s)
            tokens = np.zeros((self.width,), np.int32)
            nxt = np.zeros((self.width,), np.int32)
            tokens[:len(seq)] = seq
            nxt[:len(seq) - 1] = seq[1:]
            live, seam = np.int32(len(seq)), np.int32(len(p))
            x = self._stack(w, tokens, live, seam, False)
            x_low = self._stack(w, tokens, live, seam, self.control) \
                if self.control else x
            g_served, g_ctrl = (np.asarray(g) for g in
                                self._gaps(top, x, x_low, nxt))
            span = slice(len(p) - 1, len(p) - 1 + len(s))
            served.append(g_served[span])
            control.append(g_ctrl[span])
        return served, control

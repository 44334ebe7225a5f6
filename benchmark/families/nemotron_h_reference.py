"""The plain reference of family ``nemotron_h``: a decoder whose every layer
is ONE sub-block under a pre-norm residual, a Mamba-2 mixer (``M``), a latent
top-k expert feed-forward (``E``) or attention without any positional term
(``*``), as ``hybrid_override_pattern`` spells them; in straightforward
``jax.numpy``, float32 under ``precision="highest"``. No kernel, no cache, no
batching, the state-space recurrence TOKEN BY TOKEN (never in chunks: it must
not share the program's algorithm), nothing imported from the program under
test. It owns the weights: ``make_weights`` draws every tensor from the seed,
rounds it to bfloat16 (the precision the configuration stores), and the
family hands the program the same arrays.

The equations (``x`` a row of ``hidden_size``; as published unless the
configuration's ``assumed`` says otherwise):

    n(x)  = x * rsqrt(mean(x^2) + eps) * g              RMS norm, float32
    layer   x' = x + f(n(x)),  f the layer's one sub-block
    head    logits = n(x) Whead                          untied

    M   [z | xBC | dt] = u Win   (widths H P, H P + 2 G N, H)
        xBC_t = silu(b + sum_{j<4} w_j * xBC_{t-3+j})   depthwise, causal,
                                                        zeros before the start
        [x | B | C] = xBC;  dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t   state (H, P, N), h_0 = 0
        y_t = h_t C_t + D x_t                        head i in group i // (H/G)
        y = y * silu(z), RMS-normalised over each group's channels APART
            (eps) times a gain;  f = y Wout             gate THEN norm
    *   q = u Wq, k = u Wk, v = u Wv; query head i reads KV head
        i // (heads / kv_heads); scale 1/sqrt(head_dim); causal; NO positional
        term;  f = concat(heads) Wo
    E   s = sigmoid(u Wr) over ALL router_width experts; the
        num_experts_per_tok largest of s + b;  w = s_pick / sum(s_pick) *
        routed_scaling_factor;  l = u Wdown (latent);
        E_e(l) = relu(l W1_e)^2 W2_e
        f = (sum_{pick and held} w_e E_e(l)) Wup + relu(u Ws1)^2 Ws2

THE SHARE: the configuration is one chip's part of every layer (its routed
experts of the router's width, its Mamba heads with their groups, its query
heads and their KV head, its rows of the vocabulary). A token's weights are
normalised over all of its picks and only the held picks add; ``Wup`` is
applied to that partial sum; the gated norm is a group's own, so a chip's
groups need no other chip's. Nothing stands in for what the absent chips
would add, and the partial ``x'`` goes on. ``parts`` gives a sub-block's
output as (what the chips' shares sum to, what every chip computes alike) for
the test that adds the shares up.

Layout (per layer kind ``k`` with ``n`` layers of it; linear weights ``(in,
out)``; every tensor bfloat16):

    embedding (V, hidden)  lm_head (hidden, V)  final_norm (hidden,)
    layers["mamba"]: norm (n, hidden)  in_proj (n, hidden, 2 H P + 2 G N + H)
        conv_w (n, H P + 2 G N, 4)  conv_b  dt_bias, A_log, D (n, H)
        gate_norm (n, H P)  out_proj (n, H P, hidden)
    layers["attention"]: norm  wq (n, hidden, heads d)  wk, wv (n, hidden,
        kv d)  wo (n, heads d, hidden)
    layers["moe"]: norm  router (n, hidden, router_width)  router_bias (n,
        router_width)  w_latent_down (n, hidden, latent)  w_latent_up
        w_up (n, held, latent, F)  w_down (n, held, F, latent)
        shared_up (n, hidden, Fs)  shared_down (n, Fs, hidden)

At published widths the float32 image of the weights does not fit a chip
beside the activations, so ``ServeReference`` walks the layers and converts
one layer's tensors at a time; every request is padded to ONE width, so a
layer kind compiles once a run.

``control``: ``"int8"`` / ``"fp8"`` round both operands of every linear
product (projections, experts, head) to that grid, scaled by the tensor's
absmax: the precision below the bfloat16 the configuration states.
``"fp8_routed"`` rounds the routed experts' two products (``W1_e``, ``W2_e``
and their inputs) alone and nothing else: what an fp8 expert path in the
program would do, read beside the limit by ``scripts/calibrate_serve_faults.py
--modes fp8_routed`` (PERF.md section 2). The names in ``FAULTS`` plant one
fault of arithmetic each in the same place, so that tests and calibration
can show the comparison refuses them.
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
FAULTS = ("conv_tail_dropped", "gate_after_norm", "scaling_left_out",
          "bias_in_weights", "held_norm", "norm_over_all_groups")
KIND_OF = {"M": "mamba", "E": "moe", "*": "attention"}
Q_CHUNK = 128          # query rows scored at a time (bounds the score tile)
ROW_CHUNK = 512        # rows the experts take at a time
WIDTH_STEP = 512       # the one width of a run is a multiple of this

# THE INIT IS PART OF THE YARDSTICK: each sub-block has to add about as much
# to the residual as the others, or a fault in the small one moves no served
# token (PERF.md section 2). With std = initializer_range, a normalised input
# u (rms ~1) and these scales the mixer adds rms ~0.45 a layer, the attention
# ~0.3, the shared expert ~0.2 and the routed sum ~0.1:
WO_GAIN = 2.0           # attention's Wo ~ N(0, WO_GAIN * std): the softmax
#                         averages its values down over the context
OUT_GAIN = 0.5          # Mamba's Wout ~ N(0, OUT_GAIN * std): its input is
#                         RMS-normalised to 1 over 1,024 channels a group
ROUTED_GAIN = 0.4       # W2_e and Wup ~ N(0, ROUTED_GAIN * std): the routed
#                         sum adds rms ~0.05 a layer. With 22 picks of 512
#                         the scores at the 22nd lie 0.0025 apart, bfloat16's
#                         own 0.5% moves a pick for a quarter of the tokens a
#                         layer, and each moved pick is a whole expert's
#                         output: at 1.15 (rms 0.35, on a par with the
#                         others) the PROGRAM read 15% off the reference's
#                         logits over five layers on the chip, at 0.6 its
#                         token_gap_mean still read 0.014-0.017 beside a
#                         dropped conv tail's 0.029-0.041 (PERF.md section
#                         2). Small enough that those flips stay near
#                         bfloat16's floor, large enough that the scaling
#                         left out (x 0.2) and the weights normalised over
#                         the held picks (x 4) still move the logits. No
#                         value makes the cell guard the experts' precision:
#                         fp8 in the routed products alone ("fp8_routed")
#                         reads UNDER the program at 0.4 (0.0013-0.0016
#                         beside 0.0024-0.0029) and at 0.6 (0.012-0.014
#                         beside 0.016) on the chip: the program's gap is
#                         the router's, and both grow with this scale
SHARED_DOWN_GAIN = 0.07 # Ws2 ~ N(0, SHARED_DOWN_GAIN * std): relu^2 of a
#                         4096-wide product is ~2 a channel over 5,376
BIAS_STD = 0.01         # router_bias ~ N(0, router_bias_std or this): enough
#                         to move the choice at the 22nd pick (the scores there
#                         lie ~0.0025 apart) and too little to skew the load.
#                         Counted in the WEIGHTS it moves them by 1%, which
#                         no served token shows; a bias that would show there
#                         (~0.3, the size of the scores) decides the choice
#                         by itself and loads the experts 7:1 (PERF.md
#                         section 2): the tiny CPU cell sets 0.3
CONV_STD = 0.5          # the conv's four taps alike, so the tail matters
D_SPREAD = 1.0          # D = exp(N(0, D_SPREAD)) a head: the groups' scales
#                         differ, so a norm over all groups at once shows
DT_RANGE = (0.003, 0.1)  # dt_bias = softplus^-1(log-uniform): the published
#                          time_step_min/max shape the init only
A_RANGE = (1.0, 16.0)   # A_log = log(uniform): Mamba-2's init


def layer_types(cfg):
    """The held layers' kinds by the names the pattern's letters map to."""
    return tuple(KIND_OF[c] for c in
                 cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]])


def kinds(cfg):
    """Layer kinds in order of first appearance -> how many layers."""
    types = layer_types(cfg)
    return {k: types.count(k) for k in dict.fromkeys(types)}


def held_experts(cfg):
    """The ids of the routed experts held here (the configuration counts
    them: ``n_routed_experts`` from ``first_held_expert``, 0 when absent)."""
    first = cfg.get("first_held_expert", 0)
    return list(range(first, first + cfg["n_routed_experts"]))


def mamba_sizes(cfg):
    """``(inner H P, conv channels H P + 2 G N, G N)``."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return inner, inner + 2 * gn, gn


def weight_shapes(cfg):
    H, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    nh = cfg["mamba_num_heads"]
    inner, conv, _ = mamba_sizes(cfg)
    n, lat = cfg["n_routed_experts"], cfg["moe_latent_size"]
    F, Fs = cfg["moe_intermediate_size"], \
        cfg["moe_shared_expert_intermediate_size"]
    per_layer = {
        "mamba": {"norm": (H,), "in_proj": (H, inner + conv + nh),
                  "conv_w": (conv, cfg["conv_kernel"]), "conv_b": (conv,),
                  "dt_bias": (nh,), "A_log": (nh,), "D": (nh,),
                  "gate_norm": (inner,), "out_proj": (inner, H)},
        "attention": {"norm": (H,), "wq": (H, q), "wk": (H, kv),
                      "wv": (H, kv), "wo": (q, H)},
        "moe": {"norm": (H,), "router": (H, cfg["router_width"]),
                "router_bias": (cfg["router_width"],),
                "w_latent_down": (H, lat), "w_latent_up": (lat, H),
                "w_up": (n, lat, F), "w_down": (n, F, lat),
                "shared_up": (H, Fs), "shared_down": (Fs, H)}}
    return {"embedding": (cfg["vocab_size"], H),
            "lm_head": (H, cfg["vocab_size"]), "final_norm": (H,),
            "layers": {k: {name: (count,) + shape
                           for name, shape in per_layer[k].items()}
                       for k, count in kinds(cfg).items()}}


def _draw(name, key, shape, std, bias_std=BIAS_STD,
          routed_gain=ROUTED_GAIN):
    """One slice of tensor ``name``, float32 (the scales above; a
    configuration may state ``router_bias_std`` and ``routed_gain`` of its
    own: the tiny CPU cell does, so that its tests see every fault)."""
    normal = lambda s: s * jax.random.normal(key, shape, jnp.float32)
    if name in ("norm", "final_norm", "gate_norm"):
        return 1.0 + normal(std)
    if name == "conv_w":
        return normal(CONV_STD)
    if name == "conv_b":
        return normal(0.1)
    if name == "router_bias":
        return normal(bias_std)
    if name == "D":
        return jnp.exp(normal(D_SPREAD))
    if name == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
    if name == "dt_bias":
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1
    gain = {"wo": WO_GAIN, "out_proj": OUT_GAIN, "w_down": routed_gain,
            "w_latent_up": routed_gain,
            "shared_down": SHARED_DOWN_GAIN}.get(name, 1.0)
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
                         cfg.get("router_bias_std", BIAS_STD),
                         cfg.get("routed_gain", ROUTED_GAIN)
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


# -- M ------------------------------------------------------------------------


def mamba(cfg, lp, u, mode, seam=None):
    """The Mamba-2 mixer over the rows of ``u``, the recurrence one token a
    step (padding rows come last and are read by no real row). ``seam``
    (fault ``conv_tail_dropped``): the position of the first
    decoded token, whose conv, and the next two's, read zeros where the
    prompt's last inputs belong."""
    T = u.shape[0]
    eps = cfg["layer_norm_epsilon"]
    nh, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    inner, conv, gn = mamba_sizes(cfg)
    proj = _linear(u, lp["in_proj"], mode)
    z, xbc, dt = jnp.split(proj, (inner, inner + conv), axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, conv), jnp.float32), xbc], 0)
    w = lp["conv_w"].astype(jnp.float32)
    acc = lp["conv_b"].astype(jnp.float32)[None, :]
    pos = jnp.arange(T)
    for j in range(K):
        tap = padded[j:j + T]
        if mode == "conv_tail_dropped":
            source = pos - (K - 1) + j
            tap = jnp.where(((pos >= seam) & (source < seam))[:, None], 0.0,
                            tap)
        acc = acc + tap * w[None, :, j]
    xbc = jax.nn.silu(acc)
    x, B, C = jnp.split(xbc, (inner, inner + gn), axis=-1)
    x = x.reshape(T, nh, P)
    B = jnp.repeat(B.reshape(T, G, N), nh // G, axis=1)
    C = jnp.repeat(C.reshape(T, G, N), nh // G, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))

    def step(h, row):
        x_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((nh, P, N), jnp.float32),
                        (x, dt, B, C))
    y = y + lp["D"].astype(jnp.float32)[None, :, None] * x
    y = y.reshape(T, inner)
    groups = 1 if mode == "norm_over_all_groups" else G

    def group_norm(v):
        g = v.reshape(T, groups, -1)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + eps)
        return g.reshape(T, inner) * lp["gate_norm"].astype(jnp.float32)

    if mode == "gate_after_norm":
        y = group_norm(y) * jax.nn.silu(z)
    else:
        y = group_norm(y * jax.nn.silu(z))
    return _linear(y, lp["out_proj"], mode)


# -- * ------------------------------------------------------------------------


def attention(cfg, lp, u, mode, live=None):
    T = u.shape[0]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = _linear(u, lp["wq"], mode).reshape(T, nh, d)
    k = jnp.repeat(_linear(u, lp["wk"], mode).reshape(T, nkv, d),
                   nh // nkv, axis=1)
    v = jnp.repeat(_linear(u, lp["wv"], mode).reshape(T, nkv, d),
                   nh // nkv, axis=1)
    col = jnp.arange(T)
    chunk = Q_CHUNK if T % Q_CHUNK == 0 else T     # a short one whole

    def rows(start):
        qc = jax.lax.dynamic_slice_in_dim(q, start, chunk, 0)
        s = jnp.einsum("qhd,khd->hqk", qc, k, precision=HI) / math.sqrt(d)
        dead = col[None, :] > (start + jnp.arange(chunk))[:, None]
        p = jax.nn.softmax(jnp.where(dead[None], -jnp.inf, s), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v,
                          precision=HI).reshape(chunk, nh * d)

    ctx = _over_rows(rows, T, chunk, nh * d, live)
    return _linear(ctx, lp["wo"], mode)


# -- E ------------------------------------------------------------------------


def expert_parts(cfg, lp, u, mode):
    """``(routed, shared)`` over the rows of ``u``: the held part of the
    routed sum through ``Wup`` (the chips' shares of it add up to the
    layer's) and the shared expert (every chip computes it alike)."""
    held = jnp.asarray(held_experts(cfg))
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.matmul(u, lp["router"].astype(jnp.float32),
                                       precision=HI))
    bias = lp["router_bias"].astype(jnp.float32)[None, :]
    _, top_e = jax.lax.top_k(scores + bias, k)
    top_s = jnp.take_along_axis(
        scores + bias if mode == "bias_in_weights" else scores, top_e, -1)
    is_held = jnp.isin(top_e, held)
    if mode == "held_norm":        # fault: normalised over held picks only
        total = jnp.maximum(jnp.sum(jnp.where(is_held, top_s, 0.0), -1,
                                    keepdims=True), 1e-20)
    else:
        total = jnp.sum(top_s, axis=-1, keepdims=True)
    weight = top_s / total * (1.0 if mode == "scaling_left_out"
                              else cfg["routed_scaling_factor"])
    # (T, held): the weight a token gives each held expert, 0 if not picked
    per = jnp.sum(jnp.where(top_e[..., None] == held, weight[..., None],
                            0.0), axis=1)
    latent = _linear(u, lp["w_latent_down"], mode)
    routed_mode = "fp8" if mode == "fp8_routed" else mode

    def one(acc, e):
        w1, w2, pe = e
        mid = jnp.square(jax.nn.relu(_linear(latent, w1, routed_mode)))
        return acc + pe[:, None] * _linear(mid, w2, routed_mode), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                             (lp["w_up"], lp["w_down"], per.T))
    shared = _linear(jnp.square(jax.nn.relu(
        _linear(u, lp["shared_up"], mode))), lp["shared_down"], mode)
    return _linear(routed, lp["w_latent_up"], mode), shared


def experts(cfg, lp, u, mode, live=None):
    """The expert layer over ``u``, a chunk of rows at a time."""
    T, H = u.shape
    chunk = ROW_CHUNK if T % ROW_CHUNK == 0 else T
    return _over_rows(
        lambda start: sum(expert_parts(
            cfg, lp, jax.lax.dynamic_slice_in_dim(u, start, chunk, 0),
            mode)), T, chunk, H, live)


# -- the stack ----------------------------------------------------------------


def sub_block(cfg, lp, u, kind, mode, live=None, seam=None):
    if kind == "mamba":
        return mamba(cfg, lp, u, mode, seam)
    if kind == "attention":
        return attention(cfg, lp, u, mode, live)
    return experts(cfg, lp, u, mode, live)


def layer(cfg, lp, x, kind, mode, live=None, seam=None):
    """One pre-norm residual layer over the rows of ``x``; with ``live`` (a
    traced count) the rows after the first ``live`` are padding: they
    advance no state and no real row reads them."""
    u = norm(x, lp["norm"], cfg["layer_norm_epsilon"])
    return x + sub_block(cfg, lp, u, kind, mode, live, seam)


def head(cfg, w, x, mode):
    h = norm(x, w["final_norm"], cfg["layer_norm_epsilon"])
    return _linear(h, w["lm_head"], mode)


def forward(cfg, w, tokens, mode=False, seam=None):
    """Logits ``(T, V)`` of one sequence, every layer in turn (small
    sizes: tests)."""
    x = w["embedding"][tokens].astype(jnp.float32)
    seen = dict.fromkeys(kinds(cfg), 0)
    for kind in layer_types(cfg):
        lp = {n: v[seen[kind]] for n, v in w["layers"][kind].items()}
        x = layer(cfg, lp, x, kind, mode, seam=seam)
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
        step = WIDTH_STEP if width > WIDTH_STEP else Q_CHUNK
        self.width = -(-width // step) * step
        self._layer = {
            (kind, mode): jax.jit(
                lambda lp, x, live, seam, kind=kind, mode=mode:
                layer(cfg, lp, x, kind, mode, live, seam))
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

    def _stack(self, w, tokens, live, seam, mode):
        cfg = self.cfg
        x = self._embed(w["embedding"], tokens)
        seen = dict.fromkeys(kinds(cfg), 0)
        for kind in layer_types(cfg):
            lp = {n: v[seen[kind]] for n, v in w["layers"][kind].items()}
            x = self._layer[kind, mode](lp, x, live, seam)
            seen[kind] += 1
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

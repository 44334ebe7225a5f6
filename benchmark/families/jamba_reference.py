"""The plain reference of family ``jamba``: a decoder whose every layer is TWO
sub-blocks, each under its own pre-norm residual — a mixer (a Mamba-1
selective-scan mixer, or attention without any positional term in the layers
``attn_layer_offset + k * attn_layer_period``) and then a dense gated MLP —
in straightforward ``jax.numpy``, float32 under ``precision="highest"``. No
kernel, no cache, no batching, the state-space recurrence TOKEN BY TOKEN
(never in chunks: it must not share the program's algorithm), nothing
imported from the program under test. It owns the weights: ``make_weights``
draws every tensor from the seed, rounds it to bfloat16 (the precision the
configuration stores), and the family hands the program the same arrays.

The equations (``x`` a row of ``hidden_size``; they follow ``transformers``'
``modeling_jamba.py``: ``JambaMambaMixer``, ``JambaAttentionDecoderLayer``,
``JambaMambaDecoderLayer``; the configuration's ``assumed`` lists each
departure):

    rms(x; g) = x * rsqrt(mean(x^2) + eps) * g                      float32
    layer   x = x + mixer(rms(x; g1));  h = rms(x; g2)
            x = x + (silu(h Wgate) * (h Wup)) Wdown
    end     logits = rms(x; g_final) E^T          E the embedding (tied head)

    attention   q = h Wq (heads x d), k = h Wk, v = h Wv (kv heads x d); query
        head i reads KV head i // (heads / kv_heads); NO positional term;
        softmax(q k^T / sqrt(d)) causal;  out = concat(heads) Wo
    mamba   [u0 | z] = h Win                       (hidden -> 2 E, no bias)
        u_t = silu(b + sum_{j<4} w_j * u0_{t-3+j})   depthwise, causal, zeros
                                                    before the start
        [dt | B | C] = u Wx                        (E -> R + N + N, no bias)
        dt = rms(dt; g_dt), B = rms(B; g_B), C = rms(C; g_C)    Jamba's own
        delta = softplus(dt Wdt + b_dt)            (R -> E)
        A = -exp(A_log)
        h_t[n, c] = exp(delta_t[c] A[n, c]) h_{t-1}[n, c]
                    + delta_t[c] B_t[n] u_t[c]               h_0 = 0, float32
        y_t[c] = sum_n C_t[n] h_t[n, c] + D[c] u_t[c]
        out = (y * silu(z)) Wout                   (E -> hidden, no bias)

Layout (per layer kind ``k`` with ``n`` layers of it; linear weights ``(in,
out)``; every tensor bfloat16):

    embedding (V, hidden)  final_norm (hidden,)
    every layer: norm, ff_norm (n, hidden)  mlp_gate, mlp_up (n, hidden, F)
        mlp_down (n, F, hidden)
    layers["mamba_mlp"]: in_proj (n, hidden, 2 E)  conv_w (n, E, 4)  conv_b
        x_proj (n, E, R + 2 N)  dt_norm (n, R)  b_norm, c_norm (n, N)
        dt_proj (n, R, E)  dt_bias, D (n, E)  A_log (n, N, E)  LANE-MAJOR:
        state index first, as the program holds the state  out_proj (n, E,
        hidden)
    layers["attention_mlp"]: wq (n, hidden, heads d)  wk, wv (n, hidden,
        kv d)  wo (n, heads d, hidden)

At published widths the float32 image of the weights does not fit a chip
beside the activations, so ``ServeReference`` walks the layers and converts
one layer's tensors at a time; every request is padded to ONE width, so a
layer kind compiles once a run.

``control``: ``"int8"`` / ``"fp8"`` round both operands of every linear
product (projections, MLP, head) to that grid, scaled by the tensor's
absmax: the precision below the bfloat16 the configuration states. The names
in ``FAULTS`` plant one fault of arithmetic each in the same place, so that
tests and calibration can show the comparison refuses them.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

# what every family's reference shares, written once beside the first of
# them: the seed as data, the control's rounding, a linear product in
# float32 (rounded on both sides under a control), a result built a chunk
# of rows at a time, rotary positions (here a planted fault only)
from benchmark.families.cohere2_moe_reference import (  # noqa: F401
    _linear, _over_rows, _round_to, rotary, seed_key)

HI = "highest"
FAULTS = ("norms_left_out", "conv_tail_dropped", "skip_left_out",
          "state_bf16", "rotary_on_attention", "gate_before_skip")
MAMBA, ATTENTION = "mamba_mlp", "attention_mlp"
Q_CHUNK = 128          # query rows scored at a time (bounds the score tile)
WIDTH_STEP = 256       # the one width of a run is a multiple of this

# THE INIT IS PART OF THE YARDSTICK (PERF.md section 2). Matrices are N(0,
# initializer_range) as the HF class draws them, but for:
WO_GAIN = 2.0           # attention's Wo ~ N(0, WO_GAIN * std): the softmax
#                         averages its values down over the context, and the
#                         two attention layers of 28 have to move a served
#                         token for rotary_on_attention to show
CONV_STD = 0.5          # the conv's four taps alike, so the tail matters
#                         (the HF class draws them uniform(+-0.5): the same
#                         scale, a normal's tails)
DT_RANGE = (1e-3, 1e-1)  # dt_bias = softplus^-1(log-uniform): the Mamba
#                          paper's and the HF class's init (time_step_min /
#                          time_step_max there)
# A_log[n, c] = log(n + 1), D = 1: the Mamba paper's and the HF class's init
# (S4D-real; a skip of one). dt_proj ~ uniform(+-R^-0.5): the Mamba paper's
# (the HF class leaves it N(0, std), under which delta barely depends on the
# token: sqrt(R) std = 0.25 in the softplus's argument beside a bias of
# -6.9..-2.3; at +-R^-0.5 it is 0.58 and the scan is selective). Norm gains 1
# + N(0, std), the conv's bias N(0, 0.1).
FAULT_THETA = 10000.0   # the rotary base rotary_on_attention turns with


def layer_types(cfg):
    """Layer ``i`` is attention where ``(i - attn_layer_offset) %
    attn_layer_period == 0``, else Mamba; every layer's feed-forward is the
    dense MLP (``num_experts`` 1: ``expert_layer_period`` picks among
    experts that are not there)."""
    if cfg["num_experts"] != 1:
        raise ValueError("family jamba serves the dense model "
                         f"(num_experts 1), not {cfg['num_experts']}")
    off, period = cfg["attn_layer_offset"], cfg["attn_layer_period"]
    return tuple(ATTENTION if (i - off) % period == 0 else MAMBA
                 for i in range(cfg["num_hidden_layers"]))


def kinds(cfg):
    """Layer kinds in order of first appearance -> how many layers."""
    types = layer_types(cfg)
    return {k: types.count(k) for k in dict.fromkeys(types)}


def sizes(cfg):
    """``(E inner channels, N state, R dt rank, head_dim)``."""
    return (cfg["mamba_expand"] * cfg["hidden_size"], cfg["mamba_d_state"],
            cfg["mamba_dt_rank"],
            cfg["hidden_size"] // cfg["num_attention_heads"])


def weight_shapes(cfg):
    H, F = cfg["hidden_size"], cfg["intermediate_size"]
    E, N, R, d = sizes(cfg)
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    mlp = {"norm": (H,), "ff_norm": (H,), "mlp_gate": (H, F),
           "mlp_up": (H, F), "mlp_down": (F, H)}
    per_layer = {
        MAMBA: dict(mlp, in_proj=(H, 2 * E),
                    conv_w=(E, cfg["mamba_d_conv"]), conv_b=(E,),
                    x_proj=(E, R + 2 * N), dt_norm=(R,), b_norm=(N,),
                    c_norm=(N,), dt_proj=(R, E), dt_bias=(E,),
                    A_log=(N, E), D=(E,), out_proj=(E, H)),
        ATTENTION: dict(mlp, wq=(H, q), wk=(H, kv), wv=(H, kv), wo=(q, H))}
    return {"embedding": (cfg["vocab_size"], H), "final_norm": (H,),
            "layers": {k: {name: (count,) + shape
                           for name, shape in per_layer[k].items()}
                       for k, count in kinds(cfg).items()}}


def _draw(name, key, shape, std):
    """One slice of tensor ``name``, float32 (the scales above)."""
    normal = lambda s: s * jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm"):
        return 1.0 + normal(std)
    if name == "conv_w":
        return normal(CONV_STD)
    if name == "conv_b":
        return normal(0.1)
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name == "A_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None], shape)
    if name == "dt_bias":
        lo, hi = (math.log(v) for v in DT_RANGE)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1
    if name == "dt_proj":
        bound = shape[0] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return normal((WO_GAIN if name == "wo" else 1.0) * std)


def make_weights(cfg, lo, hi):
    """Every tensor from the seed, stored bfloat16, inside one traced
    function (call it under ``jax.jit``). Each tensor is drawn one
    leading-axis slice at a time, so that the float32 draws of a
    gigabyte-sized stack never exist at once."""
    std = cfg["initializer_range"]
    base = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(cfg), is_leaf=lambda s: isinstance(s, tuple))
    out = []
    for i, (path, shape) in enumerate(leaves):
        name = path[-1].key
        stacked = len(shape) > 2
        tail = shape[1:] if stacked else shape

        def draw(j, key=jax.random.fold_in(base, i), tail=tail, name=name):
            return _draw(name, jax.random.fold_in(key, j), tail, std
                         ).astype(jnp.bfloat16)

        out.append(jax.lax.map(draw, jnp.arange(shape[0])) if stacked
                   else draw(0))
    return jax.tree_util.tree_unflatten(treedef, out)


def norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * gain.astype(jnp.float32)


# -- the mixers ---------------------------------------------------------------


def mamba(cfg, lp, h, mode, seam=None):
    """The Mamba-1 mixer over the rows of ``h``, the recurrence one token a
    step (padding rows come last and are read by no real row). ``seam``
    (fault ``conv_tail_dropped``): the position of the first decoded token,
    whose conv, and the next two's, read zeros where the prompt's last
    inputs belong."""
    T = h.shape[0]
    eps, K = cfg["rms_norm_eps"], cfg["mamba_d_conv"]
    E, N, R, _ = sizes(cfg)
    u0, z = jnp.split(_linear(h, lp["in_proj"], mode), 2, axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, E), jnp.float32), u0], 0)
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
    u = jax.nn.silu(acc)
    dt, B, C = jnp.split(_linear(u, lp["x_proj"], mode), (R, R + N), axis=-1)
    if mode != "norms_left_out":
        dt, B, C = (norm(v, lp[g], eps) for v, g in
                    ((dt, "dt_norm"), (B, "b_norm"), (C, "c_norm")))
    delta = jax.nn.softplus(_linear(dt, lp["dt_proj"], mode)
                            + lp["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(lp["A_log"].astype(jnp.float32))              # (N, E)

    def step(state, row):
        u_t, d_t, b_t, c_t = row
        state = jnp.exp(d_t[None, :] * A) * state \
            + (d_t * u_t)[None, :] * b_t[:, None]
        if mode == "state_bf16":
            state = state.astype(jnp.bfloat16).astype(jnp.float32)
        return state, jnp.sum(state * c_t[:, None], axis=0)

    _, y = jax.lax.scan(step, jnp.zeros((N, E), jnp.float32),
                        (u, delta, B, C))
    skip = lp["D"].astype(jnp.float32)[None, :] * u
    if mode == "skip_left_out":
        y = y * jax.nn.silu(z)
    elif mode == "gate_before_skip":
        y = y * jax.nn.silu(z) + skip
    else:
        y = (y + skip) * jax.nn.silu(z)
    return _linear(y, lp["out_proj"], mode)


def attention(cfg, lp, h, mode, live=None):
    T = h.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = sizes(cfg)[3]
    q = _linear(h, lp["wq"], mode).reshape(T, nh, d)
    k = _linear(h, lp["wk"], mode).reshape(T, nkv, d)
    if mode == "rotary_on_attention":
        q, k = rotary(q, FAULT_THETA), rotary(k, FAULT_THETA)
    k = jnp.repeat(k, nh // nkv, axis=1)
    v = jnp.repeat(_linear(h, lp["wv"], mode).reshape(T, nkv, d),
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


def mlp(lp, h, mode):
    mid = jax.nn.silu(_linear(h, lp["mlp_gate"], mode)) \
        * _linear(h, lp["mlp_up"], mode)
    return _linear(mid, lp["mlp_down"], mode)


# -- the stack ----------------------------------------------------------------


def layer(cfg, lp, x, kind, mode, live=None, seam=None):
    """One layer over the rows of ``x``: the mixer, then the MLP, each under
    its own pre-norm residual; with ``live`` (a traced count) the rows after
    the first ``live`` are padding: no real row reads them."""
    eps = cfg["rms_norm_eps"]
    h = norm(x, lp["norm"], eps)
    x = x + (mamba(cfg, lp, h, mode, seam) if kind == MAMBA
             else attention(cfg, lp, h, mode, live))
    return x + mlp(lp, norm(x, lp["ff_norm"], eps), mode)


def head(cfg, w, x, mode):
    h = norm(x, w["final_norm"], cfg["rms_norm_eps"])
    return _linear(h, w["embedding"].T, mode)


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
        top = {k: w[k] for k in ("embedding", "final_norm")}
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

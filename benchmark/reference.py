"""The plain reference: GPT-2 in straightforward ``jax.numpy``, float32.

No kernel, no cache, no batching tricks, and nothing imported from the
program under test. It also owns the weights: ``make_weights`` draws every
tensor from the seed in the layout written below, and the kinds hand the
program a reshaped view of the same numbers (``benchmark/kinds/*``), so
the reference never takes anything the program has made.

Layout (``L`` layers stacked on axis 0, linear weights ``(out, in)``):

    wte (V, h)  wpe (P, h)  lnf_w lnf_b (h,)
    ln1_w ln1_b ln2_w ln2_b (L, h)
    qkv_w (L, 3h, h)  qkv_b (L, 3h)     rows per head: [q_h | k_h | v_h]
    proj_w (L, h, h)  proj_b (L, h)
    fc1_w (L, f, h)   fc1_b (L, f)      fc2_w (L, h, f)  fc2_b (L, h)

``quant`` selects the control: the same arithmetic with both operands of
every matrix product of every linear layer and of the tied head rounded to
int8 or to fp8 (e4m3), scaled by the tensor's absmax — forward, and in the
backward pass the incoming gradient too, as a training step in that
precision would. Those are the precision steps below the bf16 the
configurations state, and the ones a later PR would be tempted by.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

MATMUL_PRECISION = "highest"


def seed_key(seed):
    """A PRNG key from any whole number up to 2**63: two 32-bit halves are
    folded in as data, so one compiled program serves every seed."""
    seed = int(seed)
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def _key(lo, hi):
    return jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0), lo),
                              hi)


def weight_shapes(cfg):
    V, h, L = cfg["vocab_size"], cfg["n_embd"], cfg["n_layer"]
    P, f = cfg["n_positions"], cfg["n_inner"] or 4 * cfg["n_embd"]
    return {
        "wte": (V, h), "wpe": (P, h), "lnf_w": (h,), "lnf_b": (h,),
        "ln1_w": (L, h), "ln1_b": (L, h), "ln2_w": (L, h), "ln2_b": (L, h),
        "qkv_w": (L, 3 * h, h), "qkv_b": (L, 3 * h),
        "proj_w": (L, h, h), "proj_b": (L, h),
        "fc1_w": (L, f, h), "fc1_b": (L, f),
        "fc2_w": (L, h, f), "fc2_b": (L, h),
    }


def make_weights(cfg, lo, hi):
    """Every tensor from the seed, float32, inside one traced function
    (call it under ``jax.jit``). Matrices ~ N(0, std), the two residual
    projections N(0, std / sqrt(2L)) as GPT-2 does; biases and the norms'
    parameters are drawn too (N(0, std) about 0 resp. 1), so that a fault
    in how either is applied shows in the comparison."""
    std = cfg["initializer_range"]
    key = _key(lo, hi)
    out = {}
    for i, (name, shape) in enumerate(sorted(weight_shapes(cfg).items())):
        scale = std
        if name in ("proj_w", "fc2_w"):
            scale = std / math.sqrt(2.0 * cfg["n_layer"])
        x = scale * jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.float32)
        if name in ("ln1_w", "ln2_w", "lnf_w"):
            x = 1.0 + x
        out[name] = x
    return out


def _round_to(x, mode):
    """``x`` on the grid of a lower precision, scaled by the tensor's own
    absmax: ``int8`` (127 steps each way) or ``fp8`` (e4m3, absmax at its
    largest finite value 448)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12)
    if mode == "int8":
        scale = amax / 127.0
        return jnp.clip(jnp.round(x / scale), -127.0, 127.0) * scale
    if mode == "fp8":
        scale = amax / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    raise ValueError(f"unknown control precision {mode!r}")


def _matmul(x, w):
    return jnp.einsum("...i,oi->...o", x, w, precision=MATMUL_PRECISION)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _matmul_low(x, w, mode):
    return _matmul(_round_to(x, mode), _round_to(w, mode))


def _matmul_low_fwd(x, w, mode):
    x, w = _round_to(x, mode), _round_to(w, mode)
    return _matmul(x, w), (x, w)


def _matmul_low_bwd(mode, saved, dy):
    x, w = saved
    dy = _round_to(dy, mode)
    dx = jnp.einsum("...o,oi->...i", dy, w, precision=MATMUL_PRECISION)
    dw = jnp.einsum("...o,...i->oi", dy, x, precision=MATMUL_PRECISION)
    return dx, dw


_matmul_low.defvjp(_matmul_low_fwd, _matmul_low_bwd)


def _linear(x, w, b, quant):
    """``quant``: False, or the control's precision (``"int8"``/``"fp8"``)."""
    if quant:
        y = _matmul_low(x, w, quant)
    else:
        y = _matmul(x, w)
    return y if b is None else y + b


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(cfg, quant, x, lp):
    b, s, h = x.shape
    nh = cfg["n_head"]
    d = h // nh
    eps = cfg["layer_norm_epsilon"]
    y = _layer_norm(x, lp["ln1_w"], lp["ln1_b"], eps)
    qkv = _linear(y, lp["qkv_w"], lp["qkv_b"], quant).reshape(b, s, nh, 3, d)
    q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=MATMUL_PRECISION) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                     precision=MATMUL_PRECISION).reshape(b, s, h)
    x = x + _linear(ctx, lp["proj_w"], lp["proj_b"], quant)
    y = _layer_norm(x, lp["ln2_w"], lp["ln2_b"], eps)
    y = _gelu_tanh(_linear(y, lp["fc1_w"], lp["fc1_b"], quant))
    return x + _linear(y, lp["fc2_w"], lp["fc2_b"], quant)


_LAYER_KEYS = ("ln1_w", "ln1_b", "ln2_w", "ln2_b", "qkv_w", "qkv_b",
               "proj_w", "proj_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")


def logits_fn(cfg, w, tokens, quant=False):
    """``tokens (b, s)`` -> float32 logits ``(b, s, V)``; the head is the
    tied embedding."""
    s = tokens.shape[1]
    x = w["wte"][tokens] + w["wpe"][:s]
    layers = {k: w[k] for k in _LAYER_KEYS}
    # one layer's activations live at a time: the backward pass recomputes
    # them layer by layer, which changes no number
    body = jax.checkpoint(functools.partial(_block, cfg, quant))
    x, _ = jax.lax.scan(lambda x, lp: (body(x, lp), None), x, layers)
    x = _layer_norm(x, w["lnf_w"], w["lnf_b"], cfg["layer_norm_epsilon"])
    return _linear(x, w["wte"], None, quant)


def loss_fn(cfg, w, tokens, targets, quant=False):
    """Mean next-token cross entropy over every position of the block."""
    logits = logits_fn(cfg, w, tokens, quant)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(logz - picked)


# -- training: gradients in blocks of rows, the configuration's Adam --------

def _tree(fn, *trees):
    return {k: fn(*(t[k] for t in trees)) for k in trees[0]}


class TrainReference:
    """Follows the trainer's first steps: the same rows, the mean loss over
    all of them, Adam in the L2 form (weight decay added to the gradient,
    bias-corrected) the configuration states."""

    def __init__(self, cfg, job, quant=False, rows_per_block=4,
                 keep_share=1.0, devices=None):
        self.cfg, self.job, self.quant = cfg, job, quant
        self.rows = rows_per_block
        # On several chips the same arithmetic is only PLACED differently:
        # every tensor split along one axis, the rows of a block dealt over
        # the chips (774M parameters x 16 B do not fit one chip's 16 GB).
        self.tree_sh = self.rows_sh = self.scalar_sh = None
        if devices is not None and len(devices) > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            mesh = Mesh(np.array(devices), ("d",))
            n = len(devices)

            def split(shape):
                axis = next((i for i, d in enumerate(shape) if d % n == 0),
                            None)
                spec = [None] * len(shape)
                if axis is not None:
                    spec[axis] = "d"
                return NamedSharding(mesh, P(*spec))

            self.tree_sh = {k: split(shape)
                            for k, shape in weight_shapes(cfg).items()}
            self.rows_sh = NamedSharding(mesh, P("d"))
            self.scalar_sh = NamedSharding(mesh, P())
        # the planted faults "half of the batch left out, the mean taken
        # over the rest" (0.5) and "the exchange between chips left out"
        # (1/dp: one chip's rows alone); tools/calibrate.py, never a run
        self.keep_share = keep_share

        def block_grads(w, acc, loss_sum, tokens, targets):
            loss, g = jax.value_and_grad(
                lambda w: loss_fn(cfg, w, tokens, targets, quant))(w)
            return _tree(jnp.add, acc, g), loss_sum + loss

        tree = self.tree_sh
        self._block_grads = jax.jit(
            block_grads, donate_argnums=(1, 2),
            out_shardings=None if tree is None else (tree, self.scalar_sh))

        o = job["optimizer"]
        b1, b2 = o["betas"]

        def adam(w, g, m, v, t):
            def one(w, g, m, v):
                g = g + o["weight_decay"] * w
                m = b1 * m + (1.0 - b1) * g
                v = b2 * v + (1.0 - b2) * g * g
                upd = (m / (1.0 - b1 ** t)) / (
                    jnp.sqrt(v / (1.0 - b2 ** t)) + o["eps"])
                return w - o["lr"] * upd, m, v
            out = _tree(one, w, g, m, v)
            return tuple({k: out[k][i] for k in out} for i in range(3))

        self._adam = jax.jit(
            adam, donate_argnums=(0, 2, 3),
            out_shardings=None if tree is None else (tree, tree, tree))
        self._norms = jax.jit(lambda t: _tree(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t))
        self._diff_norms = jax.jit(lambda a, b: _tree(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))
        self._diff_norm = jax.jit(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))))

    def mean_grads(self, w, tokens, targets):
        """``tokens (rows, seq)``: mean loss and its gradient over all
        rows, accumulated ``rows_per_block`` at a time."""
        if self.keep_share < 1.0:
            tokens, targets = (x[: int(len(x) * self.keep_share)]
                               for x in (tokens, targets))
        n = tokens.shape[0] // self.rows
        acc = _tree(jnp.zeros_like, w)
        loss_sum = jnp.zeros((), jnp.float32)
        place = (lambda x: x) if self.rows_sh is None else (
            lambda x: jax.device_put(x, self.rows_sh))
        for i in range(n):
            sl = slice(i * self.rows, (i + 1) * self.rows)
            acc, loss_sum = self._block_grads(
                w, acc, loss_sum, place(tokens[sl]), place(targets[sl]))
        return float(loss_sum) / n, _tree(lambda g: g / n, acc)

    def follow(self, w0_fn, batches, against=None, keep_first_grad=False):
        """``w0_fn()`` gives the initial weights (twice: the second copy
        is the anchor of the change). Returns the losses, the per-leaf
        norm of the first gradient and of the change after the last
        step; with ``against`` (another side's first gradient, host
        arrays by leaf) also the per-leaf norm of its difference from
        this one; with ``keep_first_grad`` this side's first gradient as
        host arrays."""
        w = w0_fn()
        m, v = _tree(jnp.zeros_like, w), _tree(jnp.zeros_like, w)
        out = dict(losses=[], grad_norms=None)
        for t, (tokens, targets) in enumerate(batches, start=1):
            loss, g = self.mean_grads(w, tokens, targets)
            out["losses"].append(loss)
            if out["grad_norms"] is None:
                out["grad_norms"] = {k: float(x)
                                     for k, x in self._norms(g).items()}
                if against is not None:
                    out["grad_diff_norms"] = {
                        k: float(self._diff_norm(g[k], against[k]))
                        for k in g}
                if keep_first_grad:
                    out["first_grad"] = {k: np.asarray(x)
                                         for k, x in g.items()}
            w, m, v = self._adam(w, g, m, v, float(t))
            del g
        del m, v
        change = self._diff_norms(w, w0_fn())
        out["change_norms"] = {k: float(x) for k, x in change.items()}
        return out


# -- serving: teacher-forced logits over prompt + served tokens -------------

class ServeReference:
    """One full-sequence forward per block of rows over
    ``prompt + served tokens``; what comes back is small: at every position
    the gap of the token that was served there below the best logit and,
    for the control, the gap of the token the lower-precision forward puts
    first."""

    def __init__(self, cfg, width, rows_per_block=2, control=False):
        """``control``: False (a benchmark run never computes it) or the
        control's precision, ``"int8"`` / ``"fp8"``."""
        self.cfg, self.width, self.rows = cfg, width, rows_per_block

        def gaps(w, tokens, nxt):
            logits = logits_fn(cfg, w, tokens)
            best = jnp.max(logits, -1)
            served = jnp.take_along_axis(logits, nxt[..., None], -1)[..., 0]
            if not control:
                return best - served, jnp.zeros_like(best)
            low = jnp.argmax(logits_fn(cfg, w, tokens, quant=control), -1)
            at_low = jnp.take_along_axis(logits, low[..., None], -1)[..., 0]
            return best - served, best - at_low

        self._gaps = jax.jit(gaps)

    def gaps(self, w, prompts, streams):
        """For each request the gap of every served token below the
        reference's best logit at its position, and the same for the
        control's first choice there: two lists of 1-d arrays."""
        served, control = [], []
        for i in range(0, len(prompts), self.rows):
            rows = list(zip(prompts[i:i + self.rows],
                            streams[i:i + self.rows]))
            tokens = np.zeros((self.rows, self.width), np.int32)
            nxt = np.zeros((self.rows, self.width), np.int32)
            for r, (p, s) in enumerate(rows):
                seq = list(p) + list(s)
                tokens[r, :len(seq)] = seq
                nxt[r, :len(seq) - 1] = seq[1:]
            g_served, g_ctrl = (np.asarray(x) for x in
                                self._gaps(w, tokens, nxt))
            for r, (p, s) in enumerate(rows):
                span = slice(len(p) - 1, len(p) - 1 + len(s))
                served.append(g_served[r, span])
                control.append(g_ctrl[r, span])
        return served, control

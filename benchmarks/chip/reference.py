"""The plain reference of the dense family, in float32.

Written from the configuration's description, not from the program: token
embedding scaled by sqrt(d_model); per block RMSNorm with a (1 + scale)
gain, rotary embedding on the two halves of each head, grouped-query
causal softmax attention (query head h reads KV head h // (H / KV)), a
SwiGLU MLP, each added to the residual; a final RMSNorm and an untied
head.  Every matrix product runs at ``highest`` precision.  It imports
nothing of the program.

``fp8=True`` is the control: the same mathematics with the inputs of
every linear layer rounded to float8 e4m3 (scaled so that each weight
column's and each token's largest magnitude is e4m3's largest), the
precision below the configured bfloat16.

Training follows the deployment's AdamW: global-norm clipping, linear
warm-up, bias correction, decoupled weight decay, moments in float32 and
parameters stored in the configured type after every update.
"""
from __future__ import annotations

import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


FP8_MAX = 448.0        # largest float8_e4m3fn


def _fp8(a, axis):
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    q = (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return a + jax.lax.stop_gradient(q - a)      # straight-through


def linear(x, w, fp8=False):
    """``x @ w`` in float32; ``fp8`` rounds both inputs to float8 first."""
    if fp8:
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.einsum("...d,df->...f", x, w, precision=HI)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def rope(x, pos, theta):
    """x: (B, S, H, hd); pos: (S,)."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(hd // 2, dtype=jnp.float32)
                            / (hd // 2))
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (S, hd/2)
    c, s = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def block(c: dict, lw: dict, x, fp8=False):
    """One decoder block on x: (B, S, D), weights of one layer."""
    B, S, D = x.shape
    H, KV, hd = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    eps, theta = c["norm_eps"], c["rope_theta"]
    pos = jnp.arange(S)
    h = rmsnorm(x, lw["ln1"], eps)
    q = rope(linear(h, lw["wq"], fp8).reshape(B, S, H, hd), pos, theta)
    k = rope(linear(h, lw["wk"], fp8).reshape(B, S, KV, hd), pos, theta)
    v = linear(h, lw["wv"], fp8).reshape(B, S, KV, hd)
    q = q.reshape(B, S, KV, H // KV, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", a, v, precision=HI)
    x = x + linear(o.reshape(B, S, H * hd), lw["wo"], fp8)
    h = rmsnorm(x, lw["ln2"], eps)
    m = jax.nn.silu(linear(h, lw["wg"], fp8)) * linear(h, lw["wu"], fp8)
    return x + linear(m, lw["wd"], fp8)


LAYER = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")


def embed(c: dict, w: dict, tokens):
    return w["embed"][tokens].astype(jnp.float32) * math.sqrt(c["d_model"])


def head(c: dict, w: dict, x, fp8=False):
    x = rmsnorm(x, w["final_norm"].astype(jnp.float32), c["norm_eps"])
    return linear(x, w["unembed"].astype(jnp.float32), fp8)


def forward_hidden(c: dict, w: dict, tokens, fp8=False):
    """Final residual (before the last norm), layers scanned with a
    checkpoint each so that training fits."""
    stack = {k: w[k].astype(jnp.float32) for k in LAYER}
    body = jax.checkpoint(lambda x, lw: (block(c, lw, x, fp8), None))
    x, _ = jax.lax.scan(body, embed(c, w, tokens), stack)
    return x


def nll_sum(c: dict, w: dict, tokens, labels, fp8=False, chunk=256):
    """Summed negative log-likelihood of ``labels`` (all counted)."""
    x = forward_hidden(c, w, tokens, fp8)
    S = x.shape[1]
    total = 0.0
    for i in range(0, S, chunk):
        piece = jax.checkpoint(lambda xc, lc: _nll(c, w, xc, lc, fp8))
        total = total + piece(x[:, i:i + chunk], labels[:, i:i + chunk])
    return total


def _nll(c, w, xc, lc, fp8):
    logits = head(c, w, xc, fp8)
    lse = jax.scipy.special.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, lc[..., None], -1)[..., 0]
    return jnp.sum(lse - tgt)


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def make_grad(c: dict, fp8=False):
    """jitted (w, tokens, labels) -> (nll_sum, grads of nll_sum), float32
    compute on the given weights."""
    def f(w32, tokens, labels):
        return nll_sum(c, w32, tokens, labels, fp8)
    return jax.jit(jax.value_and_grad(f))


def loss_and_grad(grad_fn, w, tokens, labels, rows_per_block: int):
    """Mean loss and its gradient over all rows, in blocks of rows."""
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    n = tokens.shape[0] * tokens.shape[1]
    tot, grads = 0.0, None
    for r in range(0, tokens.shape[0], rows_per_block):
        l, g = grad_fn(w32, tokens[r:r + rows_per_block],
                       labels[r:r + rows_per_block])
        tot = tot + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return tot / n, jax.tree.map(lambda g: g / n, grads)


@partial(jax.jit, static_argnums=(0, 1))
def adamw(opt: tuple, dtype, w, mu, nu, g, t):
    """One AdamW update; ``opt`` = (lr, b1, b2, eps, wd, clip, warmup)."""
    lr0, b1, b2, eps, wd, clip, warmup = opt
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-9)) if clip else 1.0
    g = jax.tree.map(lambda x: x * scale, g)
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    tf = t.astype(jnp.float32)
    lr = lr0 * jnp.minimum(tf / max(warmup, 1), 1.0)
    bc1, bc2 = 1 - b1 ** tf, 1 - b2 ** tf

    def upd(p, m, v):
        p32 = p.astype(jnp.float32)
        d = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p32
        return (p32 - lr * d).astype(dtype)
    return jax.tree.map(upd, w, mu, nu), mu, nu, g


def train(c: dict, opt: tuple, w, batches, steps: int, rows_per_block: int,
          fp8=False):
    """``steps`` AdamW steps from ``w`` on ``batches[i] = (tokens,
    labels)``: the loss of each, the clipped gradient of the first, and
    the parameters after the last."""
    grad_fn = make_grad(c, fp8)
    dtype = jnp.dtype(c["dtype"])
    mu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)
    nu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), w)
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for i in range(steps):
            loss, g = loss_and_grad(grad_fn, w, *batches[i], rows_per_block)
            w, mu, nu, g = adamw(opt, dtype, w, mu, nu, g,
                                 jnp.asarray(i + 1, jnp.int32))
            losses.append(float(loss))
            if first is None:
                first = g
    return losses, first, w


# ---------------------------------------------------------------------------
# serving: layer by layer, so that the float32 weights of one layer at a
# time are on the device.  Given ``devices``, the rows are split over them
# and each layer's weights, wherever they are drawn, are gathered whole
# onto each: every chip runs its own rows.
# ---------------------------------------------------------------------------

def _placement(devices):
    """Shardings of the rows and of a layer's weights over ``devices``
    (None for JAX's default device)."""
    if devices is None:
        return None, None
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("rows",))
    return NamedSharding(mesh, P("rows")), NamedSharding(mesh, P())


def _put(a, sharding):
    return a if sharding is None else jax.device_put(a, sharding)


@lru_cache(maxsize=None)
def _placer(whole):
    return jax.jit(lambda tree: tree, out_shardings=whole)


def _whole(tree, whole):
    """``tree`` placed by ``whole`` on the devices themselves: an
    all-gather where a leaf is split (``device_put`` would take a split
    leaf through the host, a layer at a time)."""
    return tree if whole is None else _placer(whole)(tree)


def _items(c: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str))))


@partial(jax.jit, static_argnums=(0, 3))
def _layer(cfg_items, lw, x, fp8):
    c = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        return block(c, {k: a.astype(jnp.float32) for k, a in lw.items()},
                     x, fp8)


@partial(jax.jit, static_argnums=(0,))
def _embed(cfg_items, table, tokens):
    return embed(dict(cfg_items), {"embed": table}, tokens)


def hidden_layerwise(c: dict, w: dict, tokens, fp8=False, devices=None):
    """Final residual of ``tokens`` (B, T), one layer at a time; ``B`` a
    multiple of the number of ``devices``."""
    rows, whole = _placement(devices)
    items = _items(c)
    x = _embed(items, _whole(w["embed"], whole), _put(tokens, rows))
    for i in range(c["n_layers"]):
        x = _layer(items, _whole({k: w[k][i] for k in LAYER}, whole), x,
                   fp8)
    return x


@partial(jax.jit, static_argnums=(0,))
def _gap_served(cfg_items, fn_w, un_w, x, served):
    """Reference logit of the best token minus that of ``served``."""
    c = dict(cfg_items)
    logits = head(c, {"final_norm": fn_w, "unembed": un_w}, x)
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[..., None], -1)[..., 0]
    return best - got


@partial(jax.jit, static_argnums=(0,))
def _gap_control(cfg_items, fn_w, un_w, x_ref, x_fp8):
    """Reference gap of the token that the fp8 control puts first."""
    c = dict(cfg_items)
    w = {"final_norm": fn_w, "unembed": un_w}
    ref = head(c, w, x_ref)
    pick = jnp.argmax(head(c, w, x_fp8, fp8=True), -1)
    got = jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return jnp.max(ref, -1) - got


def _by_blocks(fn, devices, rows: int, *arrays):
    """``fn`` over blocks of ``rows`` rows per device, the last block
    filled up with copies of its first row; the results concatenated."""
    n = rows * (len(devices) if devices is not None else 1)
    out = []
    for r in range(0, arrays[0].shape[0], n):
        block = [a[r:r + n] for a in arrays]
        k = block[0].shape[0]
        if k < n:
            block = [jnp.concatenate([b] + [b[:1]] * (n - k)) for b in block]
        out.append(fn(*block)[:k])
    return jnp.concatenate(out, 0)


def served_gaps(c: dict, w: dict, tokens, served, rows: int = 1,
                devices=None):
    """Per position, how far the reference logit of the next served token
    lies below the reference's best.  ``tokens``: (B, T) prompt and served
    tokens; ``served``: (B, T), the token that followed each position;
    ``rows`` per device at a time."""
    rows_s, whole = _placement(devices)
    items = _items(c)
    fn_w, un_w = _whole((w["final_norm"], w["unembed"]), whole)

    def gaps(tok, srv):
        x = hidden_layerwise(c, w, tok, devices=devices)
        with jax.default_matmul_precision("highest"):
            return _gap_served(items, fn_w, un_w, x, _put(srv, rows_s))
    return _by_blocks(gaps, devices, rows, tokens, served)


def control_gaps(c: dict, w: dict, tokens, rows: int = 1, devices=None):
    """Per position, the reference gap of the fp8 control's first
    choice."""
    _, whole = _placement(devices)
    items = _items(c)
    fn_w, un_w = _whole((w["final_norm"], w["unembed"]), whole)

    def gaps(tok):
        x = hidden_layerwise(c, w, tok, devices=devices)
        xq = hidden_layerwise(c, w, tok, fp8=True, devices=devices)
        with jax.default_matmul_precision("highest"):
            return _gap_control(items, fn_w, un_w, x, xq)
    return _by_blocks(gaps, devices, rows, tokens)

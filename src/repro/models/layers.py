"""Shared model building blocks (pure JAX, config-driven).

Parameter trees are built from *leaf specs* — one source of truth giving
shape, logical sharding axes, and init scale — so random init (smoke tests),
abstract init (dry-run), and shardings all derive from the same structure.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..sharding.specs import constrain
from .config import ModelConfig


# ---------------------------------------------------------------------------
# Leaf specs
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class Leaf:
    shape: tuple
    logical: tuple
    scale: float = 1.0          # stddev multiplier (fan-in scaling applied)
    dtype: Optional[str] = None


def is_leaf(x):
    return isinstance(x, Leaf)


def init_tree(spec, rng, dtype):
    leaves, treedef = jax.tree.flatten(spec, is_leaf=is_leaf)
    keys = jax.random.split(rng, len(leaves))
    out = []
    for k, lf in zip(keys, leaves):
        dt = lf.dtype or dtype
        fan_in = lf.shape[-2] if len(lf.shape) >= 2 else lf.shape[-1]
        if lf.scale == 0.0:
            out.append(jnp.zeros(lf.shape, dt))
        elif lf.scale == -1.0:   # ones (norm scales)
            out.append(jnp.ones(lf.shape, dt))
        else:
            std = lf.scale / math.sqrt(max(fan_in, 1))
            out.append((jax.random.normal(k, lf.shape, jnp.float32)
                        * std).astype(dt))
    return jax.tree.unflatten(treedef, out)


def abstract_tree(spec, dtype):
    return jax.tree.map(
        lambda lf: jax.ShapeDtypeStruct(lf.shape, lf.dtype or dtype),
        spec, is_leaf=is_leaf)


def logical_tree(spec):
    return jax.tree.map(lambda lf: lf.logical, spec, is_leaf=is_leaf)


def stacked(leaf: Leaf, n: int) -> Leaf:
    """Stack a leaf along a leading scan axis."""
    return Leaf((n,) + leaf.shape, ("layers",) + leaf.logical, leaf.scale,
                leaf.dtype)


def stack_spec(spec, n: int):
    return jax.tree.map(lambda lf: stacked(lf, n), spec, is_leaf=is_leaf)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def norm_spec(d: int) -> Leaf:
    return Leaf((d,), ("embed",), scale=0.0)


# ---------------------------------------------------------------------------
# RoPE (incl. M-RoPE for the VLM backbone)
# ---------------------------------------------------------------------------

def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd // 2, dtype=jnp.float32)
                            / (hd // 2)))


def rope_angles(positions, hd: int, theta: float, mrope_sections=None):
    """positions: (..., S) int or (..., S, 3) for M-RoPE -> (..., S, hd//2)."""
    freqs = rope_freqs(hd, theta)
    if mrope_sections is None:
        return positions[..., None].astype(jnp.float32) * freqs
    # M-RoPE (Qwen2-VL): frequency bands partitioned into (t, h, w) sections,
    # each rotated by its own position stream.
    sec = mrope_sections
    assert sum(sec) == hd // 2
    parts = []
    off = 0
    for i, s in enumerate(sec):
        p = positions[..., i].astype(jnp.float32)
        parts.append(p[..., None] * freqs[off:off + s])
        off += s
    return jnp.concatenate(parts, axis=-1)


def apply_rope(x, angles):
    """x: (B, S, H, hd); angles: (B, S, hd//2)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    c = jnp.cos(angles)[:, :, None, :]
    s = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


# ---------------------------------------------------------------------------
# Attention (GQA, optional sliding window / bidirectional / softcap)
# ---------------------------------------------------------------------------

def attn_spec(cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    spec = {
        "wq": Leaf((d, h * hd), ("embed_fsdp", "heads")),
        "wk": Leaf((d, kv * hd), ("embed_fsdp", "kv_heads")),
        "wv": Leaf((d, kv * hd), ("embed_fsdp", "kv_heads")),
        "wo": Leaf((h * hd, d), ("heads", "embed_fsdp")),
    }
    if cfg.use_bias:
        spec["bq"] = Leaf((h * hd,), ("heads",), scale=0.0)
        spec["bv"] = Leaf((kv * hd,), ("kv_heads",), scale=0.0)
        spec["bo"] = Leaf((d,), ("embed",), scale=0.0)
    return spec


def _mask(q_pos, k_pos, causal: bool, window: int):
    """q_pos: (Sq,), k_pos: (Sk,) -> (Sq, Sk) bool."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        m &= k_pos[None, :] <= q_pos[:, None]
    if window:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def gqa_attend(q, k, v, mask, softcap: float = 0.0):
    """q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd), mask broadcastable (B,1,Sq,Sk).

    With G = H // KV > 1 query heads to a KV head, each KV head's group of
    query heads is scored against its own K/V once: q is viewed as
    (B, Sq, KV, G, hd), so both products read each K/V element once and
    run on the MXU (K/V repeated to H heads would be a tensor of its own,
    multiplied on the VPU).  K/V are split over KV heads as the cache is
    (``kv_heads``); the scores, viewed as (B, H, Sq, Sk), over query heads
    as the query is (``act_heads``).  Where the rules split the query heads
    and not the KV heads, each device then scores its own query heads
    against K/V it holds whole, and nothing is gathered."""
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    if G == 1:
        scores = jnp.einsum("bqhd,bshd->bhqs", q, k)
    else:
        k = constrain(k, ("batch", None, "kv_heads", None))
        v = constrain(v, ("batch", None, "kv_heads", None))
        scores = jnp.einsum("bqkgd,bskd->bkgqs",
                            q.reshape(B, Sq, KV, G, hd), k)
        scores = scores.reshape(B, H, Sq, Sk)
    scores = scores.astype(jnp.float32)
    scores *= 1.0 / math.sqrt(hd)
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask, scores, -1e30)
    scores = constrain(scores, ("batch", "act_heads", None, None))
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    if G == 1:
        out = jnp.einsum("bhqs,bshd->bqhd", w, v)
    else:
        out = jnp.einsum("bkgqs,bskd->bqkgd",
                         w.reshape(B, KV, G, Sq, Sk), v)
    return out.reshape(B, Sq, H * hd)


ATTN_CHUNK = 1024       # q-block size for chunked attention
CHUNK_THRESHOLD = 2048  # use chunked path above this sequence length


def gqa_attend_chunked(q, k, v, q_pos, k_pos, *, causal, window,
                       softcap: float = 0.0):
    """Blockwise attention over q chunks with static per-chunk K/V slices.

    Local (sliding-window) layers only touch K/V inside the window of each
    q block, making prefill cost O(S*(window+chunk)) instead of O(S^2) —
    the TPU-side analogue of a flash-attention schedule, expressed in pure
    XLA ops (the Pallas kernel in repro.kernels is the fused variant).
    Chunks are unrolled in Python: the layer scan provides the loop.
    """
    B, Sq, H, hd = q.shape
    Sk = k.shape[1]
    c = min(ATTN_CHUNK, Sq)
    outs = []
    for s0 in range(0, Sq, c):
        s1 = min(s0 + c, Sq)
        lo = 0
        hi = Sk
        if window:
            lo = max(0, s0 - window + 1)
        if causal and Sk == Sq:
            hi = s1
        qc = q[:, s0:s1]
        m = _mask(q_pos[s0:s1], k_pos[lo:hi], causal, window)[None, None]
        outs.append(gqa_attend(qc, k[:, lo:hi], v[:, lo:hi], m, softcap))
    return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]


def attention(p, cfg: ModelConfig, x, positions, *, causal=True, window=0,
              kv_override=None, angles=None):
    """Full-sequence attention (train / prefill). Returns (y, (k, v))."""
    B, S, D = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, S, h, hd)
    if cfg.use_bias:
        q = q + p["bq"].reshape(1, 1, h, hd)
    if kv_override is None:
        ksrc = x
    else:
        ksrc = kv_override
    Sk = ksrc.shape[1]
    k = (ksrc @ p["wk"]).reshape(B, Sk, kv, hd)
    v = (ksrc @ p["wv"]).reshape(B, Sk, kv, hd)
    if cfg.use_bias:
        v = v + p["bv"].reshape(1, 1, kv, hd)
    if angles is not None:
        q = apply_rope(q, angles)
        if kv_override is None:
            k = apply_rope(k, angles)
    # inside the block, seq is gathered (SP boundary is the residual)
    q = constrain(q, ("batch", None, "act_heads", None))
    k = constrain(k, ("batch", None, None, None))
    if kv_override is None:
        kpos = positions
    else:
        kpos = jnp.arange(Sk)
    with jax.named_scope("attend"):
        if S > CHUNK_THRESHOLD:
            y = gqa_attend_chunked(q, k, v, positions, kpos, causal=causal,
                                   window=window, softcap=cfg.logit_softcap)
        else:
            m = _mask(positions, kpos, causal, window)[None, None]
            y = gqa_attend(q, k, v, m, cfg.logit_softcap)
    y = y @ p["wo"]
    if cfg.use_bias:
        y = y + p["bo"]
    return constrain(y, ("batch", "seq", "embed")), (k, v)


def decode_qkv(p, cfg: ModelConfig, x, pos, *, theta=None, rope=True):
    """The projections of one decode token: ``(q, k_new, v_new)``, each
    ``(B, 1, heads, hd)``, rotated to ``pos`` unless ``rope=False``.  The
    first piece of :func:`attention_decode`."""
    B, S1, D = x.shape
    assert S1 == 1
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    theta = theta or cfg.rope_theta
    q = (x @ p["wq"]).reshape(B, 1, h, hd)
    if cfg.use_bias:
        q = q + p["bq"].reshape(1, 1, h, hd)
    k_new = (x @ p["wk"]).reshape(B, 1, kv, hd)
    v_new = (x @ p["wv"]).reshape(B, 1, kv, hd)
    if cfg.use_bias:
        v_new = v_new + p["bv"].reshape(1, 1, kv, hd)
    if rope:
        posv = jnp.full((B, 1), pos)
        ang = rope_angles(posv, hd, theta)
        q = apply_rope(q, ang)
        k_new = apply_rope(k_new, ang)
    return q, k_new, v_new


def cache_slot(pos, C: int, window: int):
    """The cache position a token at ``pos`` is written to: ``pos % C`` in a
    ring buffer (``window`` > 0), ``pos`` in a linear cache."""
    return pos % C if window > 0 else pos


def decode_attend(p, cfg: ModelConfig, q, cache_k, cache_v, pos, *,
                  window=0):
    """Attention of one decode token over a layer's cache that already holds
    it: ``q`` from :func:`decode_qkv`, cache_{k,v}: (B, C, KV, hd).  Masks
    the slots not yet written (ring buffer or linear cache, as ``window``
    says) and returns ``y @ wo``.  The second piece of
    :func:`attention_decode`."""
    B = q.shape[0]
    C = cache_k.shape[1]
    with jax.named_scope("kv_cache"):
        idx = jnp.arange(C)
        if window > 0:
            valid = idx < jnp.minimum(pos + 1, C)
        else:
            valid = idx <= pos
        m = jnp.broadcast_to(valid[None, None, :], (B, 1, C))[:, None]
    with jax.named_scope("attend"):
        y = gqa_attend(q, cache_k, cache_v, m, cfg.logit_softcap)
    y = y @ p["wo"]
    if cfg.use_bias:
        y = y + p["bo"]
    return y


def attention_decode(p, cfg: ModelConfig, x, cache_k, cache_v, pos, *,
                     window=0, theta=None, rope=True):
    """Single-token decode. cache_{k,v}: (B, C, KV, hd). ``window`` selects
    ring-buffer semantics (C == window) vs linear cache (C == max seq).
    ``rope=False`` for families whose prefill attention runs unrotated
    (absolute/sinusoid embeddings, e.g. whisper's decoder self-attention) —
    decode must rotate exactly when prefill does, or the two paths diverge
    at every position past 0.

    :func:`decode_qkv`, the token's write into the cache, then
    :func:`decode_attend`.  The dense family, which keeps its caches
    stacked over layers, calls the two pieces itself and writes the token
    into the stack between them."""
    q, k_new, v_new = decode_qkv(p, cfg, x, pos, theta=theta, rope=rope)
    with jax.named_scope("kv_cache"):
        slot = cache_slot(pos, cache_k.shape[1], window)
        cache_k = jax.lax.dynamic_update_slice(cache_k, k_new,
                                               (0, slot, 0, 0))
        cache_v = jax.lax.dynamic_update_slice(cache_v, v_new,
                                               (0, slot, 0, 0))
    y = decode_attend(p, cfg, q, cache_k, cache_v, pos, window=window)
    return y, cache_k, cache_v


# ---------------------------------------------------------------------------
# MLP (geglu / gelu)
# ---------------------------------------------------------------------------

def mlp_spec(cfg: ModelConfig, geglu: bool = True) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    if geglu:
        return {
            "wg": Leaf((d, f), ("embed_fsdp", "ff")),
            "wu": Leaf((d, f), ("embed_fsdp", "ff")),
            "wd": Leaf((f, d), ("ff", "embed_fsdp")),
        }
    spec = {
        "w1": Leaf((d, f), ("embed_fsdp", "ff")),
        "w2": Leaf((f, d), ("ff", "embed_fsdp")),
    }
    if cfg.use_bias:
        spec["b1"] = Leaf((f,), ("ff",), scale=0.0)
        spec["b2"] = Leaf((d,), ("embed",), scale=0.0)
    return spec


def mlp(p, x):
    if "wg" in p:
        h = jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])
        h = constrain(h, ("batch", None, "act_ff"))
        return h @ p["wd"]
    h = x @ p["w1"]
    if "b1" in p:
        h = h + p["b1"]
    h = jax.nn.gelu(h)
    h = constrain(h, ("batch", None, "act_ff"))
    y = h @ p["w2"]
    if "b2" in p:
        y = y + p["b2"]
    return y


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embed_spec(cfg: ModelConfig) -> dict:
    spec = {"embed": Leaf((cfg.vocab, cfg.d_model), ("vocab", "embed_fsdp"))}
    if not cfg.tie_embeddings:
        spec["unembed"] = Leaf((cfg.d_model, cfg.vocab),
                               ("embed_fsdp", "vocab"))
    return spec


def embed(p, cfg: ModelConfig, tokens):
    with jax.named_scope("embed"):
        x = jnp.take(p["embed"], tokens, axis=0).astype(cfg.jdtype)
        if cfg.family in ("dense", "moe", "vlm"):
            x = x * math.sqrt(cfg.d_model)  # gemma-style scaling
        return constrain(x, ("batch", "seq", "embed"))


def unembed(p, cfg: ModelConfig, x):
    w = p["embed"].T if cfg.tie_embeddings else p["unembed"]
    logits = x @ w.astype(cfg.jdtype)
    # vocab-parallel logits; seq explicitly gathered (vocab CE does the
    # cross-shard logsumexp reduction)
    return constrain(logits, ("batch", None, "vocab"))

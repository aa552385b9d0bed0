"""Dense decoder-only transformer family.

Covers: gemma3-27b / gemma3-12b (5:1 local:global attention pattern,
softcap-free RoPE), yi-9b (llama arch), command-r-35b (no-bias GQA),
qwen2-vl-2b (M-RoPE + stubbed vision frontend), and the paper's GPT.

Layers are grouped by the attention *pattern* (e.g. 5 local + 1 global) and
scanned over pattern groups; any remainder layers get their own unscanned
parameter stack. Per-role KV caches (ring-buffer for "local" layers, linear
for "global") keep decode memory at the architecture's true footprint.
In decode the stacked caches are the layer loop's carry: each layer writes
its token's slot in place and attends over its own layer of the stack, so
a donated cache is never copied whole.

The device work of a step is named with ``jax.named_scope``: ``embed``,
``layers`` (the layer scan and loops), ``attn`` (with ``kv_cache`` and
``attend`` inside it, from ``layers``), ``mlp`` and ``head``.  Scopes
travel in the compiled HLO's ``op_name`` metadata and change no
instruction; the benchmark's ``scopes.py`` sums device time per scope.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from ..sharding.specs import constrain
from .config import ModelConfig
from . import layers as L


# ---------------------------------------------------------------------------
# Specs
# ---------------------------------------------------------------------------

def block_spec(cfg: ModelConfig) -> dict:
    return {
        "pre_attn": L.norm_spec(cfg.d_model),
        "attn": L.attn_spec(cfg),
        "pre_mlp": L.norm_spec(cfg.d_model),
        "mlp": L.mlp_spec(cfg, geglu=not cfg.use_bias),
    }


def model_spec(cfg: ModelConfig) -> dict:
    P = len(cfg.pattern)
    reps, tail = cfg.n_layers // P, cfg.n_layers % P
    spec = dict(L.embed_spec(cfg))
    spec["blocks"] = {f"p{i}": L.stack_spec(block_spec(cfg), reps)
                      for i in range(P)}
    if tail:
        spec["tail"] = {f"p{i}": block_spec(cfg) for i in range(tail)}
    spec["final_norm"] = L.norm_spec(cfg.d_model)
    if cfg.vision_tokens:
        spec["vision_proj"] = L.Leaf((cfg.d_model, cfg.d_model),
                                     ("embed", "embed_fsdp"))
    return spec


def _role_window(cfg, role):
    return cfg.window if role == "local" else 0


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------

def _apply_block(p, cfg, x, positions, angles, role, collect_kv=False):
    with jax.named_scope("attn"):
        h, kv_ = L.attention(p["attn"], cfg, L.rmsnorm(x, p["pre_attn"],
                                                       cfg.norm_eps),
                             positions, causal=True,
                             window=_role_window(cfg, role), angles=angles)
        x = x + h
    with jax.named_scope("mlp"):
        x = x + L.mlp(p["mlp"], L.rmsnorm(x, p["pre_mlp"], cfg.norm_eps))
    x = constrain(x, ("batch", "seq", "embed"))
    return (x, kv_) if collect_kv else (x, None)


def forward(params, cfg: ModelConfig, tokens, positions=None,
            patch_embeds=None, collect_kv=False, return_hidden=False):
    """tokens: (B, S_text); patch_embeds: (B, V_tok, D) for the VLM family.
    Returns (logits, kv_caches_or_None)."""
    B = tokens.shape[0]
    x = L.embed(params, cfg, tokens)
    if cfg.vision_tokens and patch_embeds is not None:
        pe = patch_embeds.astype(cfg.jdtype) @ params["vision_proj"]
        x = jnp.concatenate([pe, x], axis=1)
    S = x.shape[1]
    if positions is None:
        positions = jnp.arange(S)
    sections = cfg.mrope_sections if cfg.mrope else None
    if cfg.mrope:
        pos3 = jnp.broadcast_to(positions[None, :, None], (B, S, 3))
        angles = L.rope_angles(pos3, cfg.hd, cfg.rope_theta, sections)
    else:
        angles = L.rope_angles(
            jnp.broadcast_to(positions[None], (B, S)), cfg.hd, cfg.rope_theta)

    P = len(cfg.pattern)
    reps = cfg.n_layers // P
    kvs = {}

    ab = jax.checkpoint(_apply_block, static_argnums=(1, 5, 6)) \
        if cfg.remat else _apply_block

    def body(xc, blk):
        kv_list = []
        for i, role in enumerate(cfg.pattern):
            xc, kv_ = ab(blk[f"p{i}"], cfg, xc, positions, angles,
                         role, collect_kv)
            kv_list.append(kv_)
        return xc, tuple(kv_list) if collect_kv else None

    wrapped = body  # per-block checkpoints; residuals SP-sharded
    with jax.named_scope("layers"):
        if cfg.scan_layers and reps > 0:
            x, ys = jax.lax.scan(wrapped, x, params["blocks"])
            if collect_kv:
                kvs["scan"] = ys
        else:
            blocks_unstacked = [
                jax.tree.map(lambda a, g=g: a[g], params["blocks"])
                for g in range(reps)]
            ys = []
            for blk in blocks_unstacked:
                x, kv_ = wrapped(x, blk)
                ys.append(kv_)
            if collect_kv:
                kvs["scan"] = jax.tree.map(lambda *a: jnp.stack(a), *ys) \
                    if ys else None
        if "tail" in params:
            tail_kv = []
            for i, role in enumerate(cfg.pattern[:cfg.n_layers % P]):
                x, kv_ = _apply_block(params["tail"][f"p{i}"], cfg, x,
                                      positions, angles, role, collect_kv)
                tail_kv.append(kv_)
            if collect_kv:
                kvs["tail"] = tuple(tail_kv)
    with jax.named_scope("head"):
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        if return_hidden:
            return x, (kvs if collect_kv else None)
        logits = L.unembed(params, cfg, x)
        if cfg.logit_softcap:
            logits = jnp.tanh(logits / 30.0) * 30.0
    return logits, (kvs if collect_kv else None)


# ---------------------------------------------------------------------------
# Decode (single token against per-role caches)
# ---------------------------------------------------------------------------

def cache_size(cfg: ModelConfig, role: str, max_seq: int) -> int:
    return min(cfg.window, max_seq) if role == "local" else max_seq


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, abstract=False):
    """Per-pattern-position stacked KV caches.
    Layout: {"p{i}": (k, v)} with k: (reps, B, C_i, KV, hd), and the tail
    layers' unstacked {"tail{i}": (k, v)} with k: (B, C_i, KV, hd).

    :func:`decode_step` carries each stack through its layer loop and
    writes one token's slot of layer ``l`` in place, so a donated cache is
    updated where it lies and never copied whole."""
    P = len(cfg.pattern)
    reps, tail = cfg.n_layers // P, cfg.n_layers % P
    mk = (lambda s: jax.ShapeDtypeStruct(s, cfg.jdtype)) if abstract \
        else (lambda s: jnp.zeros(s, cfg.jdtype))
    cache = {}
    for i, role in enumerate(cfg.pattern):
        C = cache_size(cfg, role, max_seq)
        shape = (reps, batch, C, cfg.n_kv_heads, cfg.hd)
        cache[f"p{i}"] = (mk(shape), mk(shape))
    for i, role in enumerate(cfg.pattern[:tail]):
        C = cache_size(cfg, role, max_seq)
        shape = (batch, C, cfg.n_kv_heads, cfg.hd)
        cache[f"tail{i}"] = (mk(shape), mk(shape))
    return cache


def _decode_mlp(p, cfg, x):
    with jax.named_scope("mlp"):
        return x + L.mlp(p["mlp"], L.rmsnorm(x, p["pre_mlp"], cfg.norm_eps))


def _decode_block(p, cfg, x, ck, cv, pos, role):
    """A tail layer: its own unstacked cache (B, C, KV, hd)."""
    with jax.named_scope("attn"):
        h = L.rmsnorm(x, p["pre_attn"], cfg.norm_eps)
        h, ck, cv = L.attention_decode(p["attn"], cfg, h, ck, cv, pos,
                                       window=_role_window(cfg, role))
        x = x + h
    return _decode_mlp(p, cfg, x), ck, cv


def _row_major(a):
    """``a`` pinned to the row-major layout, the one a jitted step's
    arguments and results have."""
    return with_layout_constraint(
        a, Layout(major_to_minor=tuple(range(a.ndim))))


def _decode_stacked_block(p, cfg, x, ks, vs, l, pos, role):
    """Layer ``l`` of a stacked cache ks/vs: (reps, B, C, KV, hd).  The new
    token is written into the stack in place, then layer ``l`` is read out
    of it and attended over.

    The written stack keeps the row-major layout of the donated cache.
    Left free, the TPU compiler gives the loop's carry the layout that the
    attention's product prefers and relayouts the whole stack into the loop
    and back out of it, four copies of the whole cache a step."""
    window = _role_window(cfg, role)
    with jax.named_scope("attn"):
        h = L.rmsnorm(x, p["pre_attn"], cfg.norm_eps)
        q, k_new, v_new = L.decode_qkv(p["attn"], cfg, h, pos)
        with jax.named_scope("kv_cache"):
            slot = L.cache_slot(pos, ks.shape[2], window)
            ks = _row_major(jax.lax.dynamic_update_slice(
                ks, k_new[None], (l, 0, slot, 0, 0)))
            vs = _row_major(jax.lax.dynamic_update_slice(
                vs, v_new[None], (l, 0, slot, 0, 0)))
            ck = jax.lax.dynamic_index_in_dim(ks, l, 0, keepdims=False)
            cv = jax.lax.dynamic_index_in_dim(vs, l, 0, keepdims=False)
        x = x + L.decode_attend(p["attn"], cfg, q, ck, cv, pos,
                                window=window)
    return _decode_mlp(p, cfg, x), ks, vs


def decode_step(params, cfg: ModelConfig, cache, token, pos):
    """token: (B, 1) int32; pos: scalar int32. Returns (logits, new cache).

    The stacked caches ride in the layer loop's carry beside the residual:
    layer ``l`` writes its token's K/V into its own layer of the stack, at
    the token's slot, in place, and attends over that layer.  The loop's
    inputs are the layer parameters and ``l`` alone and it returns no
    cache, so under donation the cache is never copied whole.  The unrolled
    path (``scan_layers=False``) runs the same body with ``l`` a Python
    int."""
    x = L.embed(params, cfg, token)
    P = len(cfg.pattern)
    reps = cfg.n_layers // P

    def body(carry, blk_and_l):
        xc, stacks = carry
        blk, l = blk_and_l
        stacks = dict(stacks)
        for i, role in enumerate(cfg.pattern):
            ks, vs = stacks[f"p{i}"]
            xc, ks, vs = _decode_stacked_block(blk[f"p{i}"], cfg, xc, ks, vs,
                                               l, pos, role)
            stacks[f"p{i}"] = (ks, vs)
        return (xc, stacks), None

    stacks = {f"p{i}": cache[f"p{i}"] for i in range(P)}
    with jax.named_scope("layers"):
        if cfg.scan_layers and reps > 0:
            (x, stacks), _ = jax.lax.scan(
                body, (x, stacks), (params["blocks"], jnp.arange(reps)))
        else:
            for l in range(reps):
                blk = jax.tree.map(lambda a, l=l: a[l], params["blocks"])
                (x, stacks), _ = body((x, stacks), (blk, l))
        new_cache = dict(stacks)
        for i, role in enumerate(cfg.pattern[:cfg.n_layers % P]):
            ck, cv = cache[f"tail{i}"]
            x, ck, cv = _decode_block(params["tail"][f"p{i}"], cfg, x, ck,
                                      cv, pos, role)
            new_cache[f"tail{i}"] = (ck, cv)
    with jax.named_scope("head"):
        x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
        logits = L.unembed(params, cfg, x)
    return logits, new_cache

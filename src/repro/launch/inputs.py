"""ShapeDtypeStruct input stand-ins for every (arch x input-shape) combo.

Decode shapes lower ``serve_step`` (one token + KV cache of seq_len);
train/prefill lower full-sequence compute. Modality frontends are stubbed:
``frames`` / ``patch_embeds`` arrive as precomputed embeddings.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import dense, registry
from ..models.config import INPUT_SHAPES, InputShape, ModelConfig


def sds(shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def skip_reason(cfg: ModelConfig, shape: InputShape):
    """Return a reason string if this (arch, shape) combination is skipped
    (documented in DESIGN.md), else None."""
    if shape.name == "long_500k":
        subq = (cfg.family in ("ssm", "hybrid") or cfg.window > 0)
        if not subq:
            return ("full-attention architecture: long_500k requires "
                    "sub-quadratic attention (DESIGN.md skip table)")
    return None


def batch_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    """Model-input specs for train/prefill modes."""
    B, S = shape.global_batch, shape.seq_len
    batch = {}
    if cfg.family == "vlm":
        vt = cfg.vision_tokens
        batch["tokens"] = sds((B, S - vt))
        batch["patch_embeds"] = sds((B, vt, cfg.d_model), jnp.bfloat16)
    elif cfg.family == "audio":
        batch["tokens"] = sds((B, S))
        batch["frames"] = sds((B, cfg.encoder_frames, cfg.d_model),
                              jnp.bfloat16)
    else:
        batch["tokens"] = sds((B, S))
    if shape.mode == "train":
        batch["labels"] = sds((B, S))
    return batch


def decode_specs(cfg: ModelConfig, shape: InputShape):
    """(cache_specs, token_spec, pos_spec) for decode shapes."""
    B, S = shape.global_batch, shape.seq_len
    cache = registry.init_cache(cfg, B, S, abstract=True)
    return cache, sds((B, 1)), sds((), jnp.int32)


def input_specs(cfg: ModelConfig, shape: InputShape) -> dict:
    if shape.mode in ("train", "prefill"):
        return batch_specs(cfg, shape)
    cache, tok, pos = decode_specs(cfg, shape)
    return {"cache": cache, "token": tok, "pos": pos}


# ---------------------------------------------------------------------------
# Logical sharding axes for inputs/caches (mirrors the spec trees)
# ---------------------------------------------------------------------------

def batch_logical(cfg: ModelConfig, shape: InputShape) -> dict:
    out = {"tokens": ("batch", None)}
    if cfg.family == "vlm":
        out["patch_embeds"] = ("batch", None, "embed")
    if cfg.family == "audio":
        out["frames"] = ("batch", None, "embed")
    if shape.mode == "train":
        out["labels"] = ("batch", None)
    return out


def cache_logical(cfg: ModelConfig) -> dict:
    """Logical axes matching registry.init_cache structure."""
    fam = cfg.family
    if fam in ("dense", "vlm", "moe"):
        kv = dense.STACK_AXES
        kv_tail = kv[1:]
        out = {}
        P = len(cfg.pattern)
        for i in range(P):
            out[f"p{i}"] = (kv, kv)
        for i in range(cfg.n_layers % P):
            out[f"tail{i}"] = (kv_tail, kv_tail)
        if fam == "moe":
            out = {k: v for k, v in out.items() if k.startswith("p")}
        return out
    if fam == "ssm":
        return {
            "ssm_state": ("layers", "batch", "heads", "state", None),
            "conv_state": ("layers", "batch", "conv", "ff"),
        }
    if fam == "hybrid":
        P = len(cfg.pattern)
        reps, tail = cfg.n_layers // P, cfg.n_layers % P
        out = {}
        for i, role in enumerate(cfg.pattern):
            if role == "recurrent":
                out[f"p{i}"] = {"state": ("layers", "batch", "ff"),
                                "conv": ("layers", "batch", "conv", "ff")}
            else:
                out[f"p{i}"] = {
                    "k": ("layers", "batch", "kv_seq", "kv_heads", None),
                    "v": ("layers", "batch", "kv_seq", "kv_heads", None)}
        for i in range(tail):
            role = cfg.pattern[i]
            if role == "recurrent":
                out[f"tail{i}"] = {"state": ("batch", "ff"),
                                   "conv": ("batch", "conv", "ff")}
            else:
                out[f"tail{i}"] = {
                    "k": ("batch", "kv_seq", "kv_heads", None),
                    "v": ("batch", "kv_seq", "kv_heads", None)}
        return out
    if fam == "audio":
        ax = ("layers", "batch", "kv_seq", "kv_heads", None)
        return {"self_k": ax, "self_v": ax, "cross_k": ax, "cross_v": ax}
    raise ValueError(fam)

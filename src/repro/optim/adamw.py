"""AdamW with fp32 moments and optional global-norm clipping."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100


def init(params):
    zeros32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {
        "mu": jax.tree.map(zeros32, params),
        "nu": jax.tree.map(zeros32, params),
        "step": jnp.zeros((), jnp.int32),
    }


def schedule(cfg: AdamWConfig, step):
    warm = jnp.minimum(step.astype(jnp.float32) / max(cfg.warmup_steps, 1),
                       1.0)
    return cfg.lr * warm


def update(grads, state, params, cfg: AdamWConfig):
    with jax.named_scope("optimizer"):
        return _update(grads, state, params, cfg)


def _update(grads, state, params, cfg: AdamWConfig):
    step = state["step"] + 1
    if cfg.clip_norm:
        gnorm = jnp.sqrt(sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
    else:
        gnorm = jnp.zeros(())
    b1, b2 = cfg.b1, cfg.b2
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g.astype(jnp.float32),
                      state["mu"], grads)
    nu = jax.tree.map(
        lambda v, g: b2 * v + (1 - b2) * jnp.square(g.astype(jnp.float32)),
        state["nu"], grads)
    t = step.astype(jnp.float32)
    bc1 = 1 - b1 ** t
    bc2 = 1 - b2 ** t
    lr = schedule(cfg, step)

    def upd(p, m, v):
        mhat = m / bc1
        vhat = v / bc2
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps)
        if cfg.weight_decay:
            delta = delta + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * delta).astype(p.dtype)

    new_params = jax.tree.map(upd, params, mu, nu)
    return new_params, {"mu": mu, "nu": nu, "step": step}, gnorm

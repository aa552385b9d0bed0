"""Training step: CE loss (+ MoE aux), grad accumulation, AdamW."""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..models import registry
from ..models.config import ModelConfig
from ..optim import adamw
from ..optim.adamw import AdamWConfig


@dataclass(frozen=True)
class TrainConfig:
    optimizer: AdamWConfig = AdamWConfig()
    microbatches: int = 1          # gradient accumulation steps
    z_loss: float = 0.0


CE_CHUNKS = 8   # sequence-chunked vocab-parallel CE (bounds logits memory)


def _ce_piece(cfg, tcfg, w, xc, lc):
    """CE over one sequence chunk; logits never materialize for full S.
    It computes the logits itself, so it is the model's ``head`` scope."""
    with jax.named_scope("head"):
        logits = (xc @ w.astype(xc.dtype)).astype(jnp.float32)
        if cfg.logit_softcap:
            logits = jnp.tanh(logits / 30.0) * 30.0
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.clip(lc, 0)[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        nll = -((tgt - lse) * mask).sum()
        z = jnp.square(lse * mask).sum() if tcfg.z_loss else jnp.zeros(())
        return nll, mask.sum(), z


def make_loss_fn(cfg: ModelConfig, tcfg: TrainConfig = TrainConfig()):
    def loss_fn(params, batch):
        hidden, extras = registry.forward(params, cfg, batch,
                                          return_hidden=True)
        labels = batch["labels"]
        # VLM: hidden covers [vision tokens ; text tokens]; labels are padded
        # with ignore (-1) on the vision prefix by the pipeline/input spec.
        B, S, D = hidden.shape
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        piece = jax.checkpoint(partial(_ce_piece, cfg, tcfg, w))
        c = S // CE_CHUNKS if S % CE_CHUNKS == 0 and S >= CE_CHUNKS else S
        nll = cnt = zacc = 0.0
        for i in range(0, S, c):
            n_, c_, z_ = piece(hidden[:, i:i + c], labels[:, i:i + c])
            nll, cnt, zacc = nll + n_, cnt + c_, zacc + z_
        loss = nll / jnp.maximum(cnt, 1.0)
        if tcfg.z_loss:
            loss = loss + tcfg.z_loss * zacc / jnp.maximum(cnt, 1.0)
        metrics = {"ce_loss": loss}
        if extras and "aux_loss" in extras:
            loss = loss + extras["aux_loss"]
            metrics["aux_loss"] = extras["aux_loss"]
        metrics["loss"] = loss
        return loss, metrics
    return loss_fn


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig = TrainConfig()):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    With tcfg.microbatches > 1, the batch's leading dim is split and
    gradients are accumulated (the strategy verified in paper bug #6 — the
    accumulated loss must be scaled by 1/n_microbatches)."""
    loss_fn = make_loss_fn(cfg, tcfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def single(params, batch):
        (loss, metrics), grads = grad_fn(params, batch)
        return grads, metrics

    def accumulate(params, batch):
        n = tcfg.microbatches
        micro = jax.tree.map(
            lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)

        def body(acc, mb):
            grads, metrics = single(params, mb)
            # paper bug #6: this 1/n scaling is what buggy impls forget
            acc = jax.tree.map(lambda a, g: a + g / n, acc, grads)
            return acc, metrics

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        grads, metrics = jax.lax.scan(body, zeros, micro)
        metrics = jax.tree.map(lambda m: m.mean(), metrics)
        return grads, metrics

    def train_step(params, opt_state, batch):
        if tcfg.microbatches > 1:
            grads, metrics = accumulate(params, batch)
        else:
            grads, metrics = single(params, batch)
        params, opt_state, gnorm = adamw.update(grads, opt_state, params,
                                                tcfg.optimizer)
        metrics["grad_norm"] = gnorm
        return params, opt_state, metrics

    return train_step


def init_state(cfg: ModelConfig, rng):
    params = registry.init_params(cfg, rng)
    opt_state = adamw.init(params)
    return params, opt_state

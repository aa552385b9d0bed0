"""Faults planted under the timed path, to show that ``correct`` catches
them.  Each wraps the program's step (or the checker) as a driver's
``wrap=`` receives it; the benchmark's own runs never use them."""
from __future__ import annotations


def train_unchanged(fn):
    """A train step that returns its state unchanged."""
    def step(params, opt_state, batch):
        _, _, metrics = fn(params, opt_state, batch)
        return params, opt_state, metrics
    return step


def train_half_batch(fn):
    """A train step that leaves out the second half of the batch and takes
    the mean over the rest (masked labels do not count)."""
    def step(params, opt_state, batch):
        labels = batch["labels"]
        half = labels.shape[0] // 2
        return fn(params, opt_state,
                  dict(batch, labels=labels.at[half:].set(-1)))
    return step


def decode_token_altered(fn, every: int = 16):
    """A decode step whose produced token is altered at every
    ``every``-th position."""
    import jax.numpy as jnp

    def step(params, cache, token, pos):
        logits, cache = fn(params, cache, token, pos)
        bump = jnp.zeros(logits.shape[-1], logits.dtype) \
            .at[(pos * 7919 + 13) % logits.shape[-1]].set(1e4)
        return jnp.where(pos % every == 0, logits + bump, logits), cache
    return step


def decode_unchanged(fn):
    """A decode step that returns its KV cache unchanged."""
    def step(params, cache, token, pos):
        logits, _ = fn(params, cache, token, pos)
        return logits, cache
    return step


def verify_flipped(check, every: int = 2):
    """A checker whose every ``every``-th answer is altered: a
    certificate becomes a refutation and a refutation a certificate."""
    count = [0]

    def run(entry, engine_opts=None):
        report = check(entry, engine_opts)
        count[0] += 1
        if count[0] % every == 0:
            report.verdict = ("refinement_error"
                              if report.verdict == "certificate"
                              else "certificate")
        return report
    return run


TRAIN = {"unchanged": train_unchanged, "half_batch": train_half_batch}
DECODE = {"token_altered": decode_token_altered,
          "unchanged": decode_unchanged}
VERIFY = {"answer_altered": verify_flipped}

"""The dense decode step carries its stacked KV cache through the layer loop
and writes each layer's token in place.

- Equivalence: logits and cache equal those of the formulation that passes
  the cache to the layer scan as ``xs`` and takes it back as ``ys``, with
  ``attention_decode`` per layer (kept here as the reference), for global,
  ring-buffer local and tail layers, scanned and unrolled, at the first
  position, inside the window and past the ring's wrap.
- Structure: compiled with the cache donated, the step's entry computation
  copies no whole stacked cache and its output aliases all of the cache.
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import dense
from repro.models import layers as L
from repro.models.registry import init_params, load_config

MAX_SEQ = 32          # the global layers' cache; the local ring is 16


def _reference_decode_step(params, cfg, cache, token, pos):
    """The stacked cache through the scan's ``xs`` and back as ``ys``."""
    x = L.embed(params, cfg, token)
    P = len(cfg.pattern)
    reps = cfg.n_layers // P

    def block(p, x, ck, cv, role):
        h = L.rmsnorm(x, p["pre_attn"], cfg.norm_eps)
        h, ck, cv = L.attention_decode(p["attn"], cfg, h, ck, cv, pos,
                                       window=dense._role_window(cfg, role))
        x = x + h
        x = x + L.mlp(p["mlp"], L.rmsnorm(x, p["pre_mlp"], cfg.norm_eps))
        return x, ck, cv

    def body(xc, blk_and_cache):
        blk, caches = blk_and_cache
        new = {}
        for i, role in enumerate(cfg.pattern):
            ck, cv = caches[f"p{i}"]
            xc, ck, cv = block(blk[f"p{i}"], xc, ck, cv, role)
            new[f"p{i}"] = (ck, cv)
        return xc, new

    scan_cache = {k: v for k, v in cache.items() if k.startswith("p")}
    if cfg.scan_layers and reps > 0:
        x, new_cache = jax.lax.scan(body, x, (params["blocks"], scan_cache))
    else:
        outs = []
        for g in range(reps):
            sl = lambda a, g=g: a[g]
            x, nc = body(x, (jax.tree.map(sl, params["blocks"]),
                             jax.tree.map(sl, scan_cache)))
            outs.append(nc)
        new_cache = jax.tree.map(lambda *a: jnp.stack(a), *outs)
    new_cache = dict(new_cache)
    for i, role in enumerate(cfg.pattern[:cfg.n_layers % P]):
        ck, cv = cache[f"tail{i}"]
        x, ck, cv = block(params["tail"][f"p{i}"], x, ck, cv, role)
        new_cache[f"tail{i}"] = (ck, cv)
    x = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return L.unembed(params, cfg, x), new_cache


CONFIGS = {
    "yi-9b": ("yi-9b", {}),
    "gpt": ("gpt", {}),
    "gemma3-12b": ("gemma3-12b", {}),
    "gemma3-12b-tail": ("gemma3-12b", {"n_layers": 13}),
}


def _config(name, scan):
    arch, over = CONFIGS[name]
    return load_config(arch).reduced(scan_layers=scan, **over)


@functools.lru_cache(maxsize=None)
def _steps(name, scan):
    """The jitted step and reference of a config, shared by the positions."""
    cfg = _config(name, scan)
    return tuple(jax.jit(lambda p, c, t, s, f=f: f(p, cfg, c, t, s))
                 for f in (dense.decode_step, _reference_decode_step))


def _filled_cache(cfg, batch, seed):
    """A cache of the step's layout, every slot random, so the attention
    reads past tokens and a misplaced write shows."""
    shapes = dense.init_cache(cfg, batch, MAX_SEQ, abstract=True)
    leaves, tree = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, s.shape, s.dtype) for k, s in zip(keys, leaves)])


@pytest.mark.parametrize("pos", [0, 5, 21], ids=["first", "in_window",
                                                  "past_wrap"])
@pytest.mark.parametrize("scan", [True, False], ids=["scan", "unrolled"])
@pytest.mark.parametrize("name", list(CONFIGS))
def test_inplace_cache_matches_scanned_cache(name, scan, pos):
    cfg = _config(name, scan)
    if cfg.window:
        assert 0 < 5 < cfg.window <= 21 < MAX_SEQ
    params = init_params(cfg, jax.random.PRNGKey(0))
    cache = _filled_cache(cfg, 2, 1)
    token = jnp.array([[3], [7]], jnp.int32)
    pos = jnp.int32(pos)
    step, reference = _steps(name, scan)
    logits, new = step(params, cache, token, pos)
    ref_logits, ref = reference(params, cache, token, pos)
    assert jax.tree.structure(new) == jax.tree.structure(ref)
    np.testing.assert_allclose(logits, ref_logits, rtol=0, atol=1e-6)
    for a, b, old in zip(jax.tree.leaves(new), jax.tree.leaves(ref),
                         jax.tree.leaves(cache)):
        assert a.shape == b.shape == old.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=0, atol=1e-6)
        assert not np.array_equal(a, old)       # the token was written


def test_donated_cache_is_updated_in_place():
    cfg = load_config("yi-9b").reduced()
    B, S = 2, 64
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    cache = dense.init_cache(cfg, B, S, abstract=True)
    compiled = jax.jit(
        lambda p, c, t, s: dense.decode_step(p, cfg, c, t, s),
        donate_argnums=(1,)).lower(
            params, cache, jax.ShapeDtypeStruct((B, 1), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32)).compile()
    stacked = {s.shape for s in jax.tree.leaves(cache)}
    assert len(stacked) == 1
    shape = "f32[" + ",".join(map(str, stacked.pop())) + "]"
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    copies = [line for line in entry.splitlines()
              if re.search(r"= " + re.escape(shape) + r"\S* copy\(", line)]
    assert not copies, copies
    cache_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache_bytes

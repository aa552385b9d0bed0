"""The dense decode step carries its stacked KV cache through the layer loop
and writes each layer's token in place.

- Equivalence: logits and cache equal those of the formulation that passes
  the cache to the layer scan as ``xs`` and takes it back as ``ys``, with
  ``attention_decode`` per layer (kept here as the reference), for global,
  ring-buffer local and tail layers, scanned and unrolled, at the first
  position, inside the window and past the ring's wrap.
- Structure: compiled with the cache donated, the step's entry computation
  copies no whole stacked cache and its output aliases all of the cache.
- Tensor parallel: on a (1, 4) mesh of CPU devices, through
  ``launch/steps.build_decode`` with the cache donated, two steps give the
  logits and caches of one device, and the returned cache is still split
  by KV head.
"""
import functools
import json
import os
import re
import subprocess
import sys
from pathlib import Path

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


# two donated steps of a tiny dense model on a (1, 4) mesh of CPU devices
# and on one device; the device count has to be set before JAX starts, so
# the script runs in a process of its own
TP_SCRIPT = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from repro.launch.mesh import rules_for_config
from repro.launch.steps import build_decode
from repro.models import dense
from repro.models.config import InputShape
from repro.models.registry import init_params, load_config

devices = jax.devices("cpu")
assert len(devices) == 4, devices
cfg = load_config("yi-9b").reduced(n_heads=8, n_kv_heads=4)
B = 2
mesh = Mesh(np.array(devices).reshape(1, 4), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
fn, _, sh, donate = build_decode(
    cfg, InputShape("t", %(max_seq)d, B, "decode"), mesh,
    rules_for_config(cfg, mesh))
params = init_params(cfg, jax.random.PRNGKey(0))
shapes = dense.init_cache(cfg, B, %(max_seq)d, abstract=True)
leaves, tree = jax.tree.flatten(shapes)
keys = jax.random.split(jax.random.PRNGKey(1), len(leaves))
cache = jax.tree.unflatten(tree, [jax.random.normal(k, s.shape, s.dtype)
                                  for k, s in zip(keys, leaves)])
one = jax.jit(lambda p, c, t, s: dense.decode_step(p, cfg, c, t, s))
tp = jax.jit(fn, in_shardings=sh, donate_argnums=donate)
got = jax.device_put((params, cache), sh[:2])
out = {"logits": 0.0, "cache": 0.0}


def rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


for token, pos in (([[3], [7]], 5), ([[11], [2]], 6)):
    token, pos = jnp.array(token, jnp.int32), jnp.int32(pos)
    logits, cache = one(params, cache, token, pos)
    tp_logits, tp_cache = tp(got[0], got[1], token, pos)
    got = (got[0], tp_cache)
    out["logits"] = max(out["logits"], rel(tp_logits, logits))
    out["cache"] = max([out["cache"]] + [
        rel(a, b) for a, b in zip(jax.tree.leaves(tp_cache),
                                  jax.tree.leaves(cache))])
stack = "[" + ",".join(map(str, leaves[0].shape)) + "]"
text = tp.lower(*got, token, pos).compile().as_text()
out["gathers"] = [line.strip()[:200] for line in text.splitlines()
                  if stack in line.split(" = ", 1)[-1][:40]
                  and "all-gather" in line]
want = NamedSharding(mesh, P(None, None, None, "model", None))
out["split"] = [a.sharding.is_equivalent_to(want, a.ndim)
                for a in jax.tree.leaves(got[1])]
out["shard_shape"] = [list(a.addressable_shards[0].data.shape)
                      for a in jax.tree.leaves(got[1])]
print(json.dumps(out))
"""


def test_tensor_parallel_decode_keeps_the_cache_split():
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(root / "src"))
    p = subprocess.run([sys.executable, "-c",
                        TP_SCRIPT % {"max_seq": MAX_SEQ}],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    # the file's 1e-6, of the largest value: the all-reduce adds the four
    # devices' partial products in another order than one device does
    assert got["logits"] <= 1e-6 and got["cache"] <= 1e-6, got
    # no device gathers a whole stack, and every stack (K and V) still
    # holds one of the four KV heads a device
    assert not got["gathers"], got["gathers"]
    assert got["split"] == [True, True], got
    assert got["shard_shape"] == [[2, 2, MAX_SEQ, 1, 32]] * 2, got

"""Grouped-query attention: ``gqa_attend`` scores each KV head's group of
query heads against its own K/V, with no K/V repeated to every query head.

- Numerics: against the repeat-then-attend formulation written here, with
  the same arithmetic (products in the operands' dtype, then float32
  scores, scale, softcap, mask and softmax, weights cast to V's dtype),
  for 1, 2 and 8 query heads to a KV head, one and five query positions,
  with and without softcap, under a causal and a sliding-window mask, in
  float32 and bfloat16; and ``gqa_attend_chunked`` above
  ``CHUNK_THRESHOLD``.
- Sharding: on a (1, 4) mesh of CPU devices, with the KV heads split over
  the model axis and with only the query heads split, the same output as
  one device.
"""
import json
import math
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L

B, SK, KV, HD = 2, 16, 2, 8


def _reference(q, k, v, mask, softcap=0.0):
    """K/V repeated to every query head, then one head dim throughout."""
    G = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, G, axis=2)
    v = jnp.repeat(v, G, axis=2)
    scores = jnp.einsum("bqhd,bshd->bhqs", q, k).astype(jnp.float32)
    scores *= 1.0 / math.sqrt(q.shape[-1])
    if softcap:
        scores = jnp.tanh(scores / softcap) * softcap
    scores = jnp.where(mask, scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshd->bqhd", w.astype(v.dtype), v)
    return out.reshape(q.shape[0], q.shape[1], -1)


def _qkv(G, Sq, Sk, dtype, seed=0, kv=KV, b=B, hd=HD):
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (b, Sq, kv * G, hd), jnp.float32)
    k = jax.random.normal(kk, (b, Sk, kv, hd), jnp.float32)
    v = jax.random.normal(kv_, (b, Sk, kv, hd), jnp.float32)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype)


# float32: the products may sum in another order than the repeated form's;
# bfloat16: a product or the output may round to the neighbouring bf16
# value, one unit of bf16 rounding (2 ** -8) of the value
TOL = {jnp.float32: 1e-6, jnp.bfloat16: 2 ** -8}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("window", [0, 4], ids=["causal", "window"])
@pytest.mark.parametrize("softcap", [0.0, 1.0], ids=["nocap", "softcap"])
@pytest.mark.parametrize("Sq", [1, 5])
@pytest.mark.parametrize("G", [1, 2, 8])
def test_grouped_attention_matches_repeated_kv(G, Sq, softcap, window,
                                               dtype):
    q, k, v = _qkv(G, Sq, SK, dtype)
    q_pos = jnp.arange(SK - Sq, SK)
    mask = L._mask(q_pos, jnp.arange(SK), True, window)[None, None]
    got = jax.jit(L.gqa_attend, static_argnums=(4,))(q, k, v, mask, softcap)
    want = _reference(q, k, v, mask, softcap)
    assert got.shape == (B, Sq, KV * G * HD) and got.dtype == dtype
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    tol = TOL[dtype]
    np.testing.assert_allclose(got, want, atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [0, 512], ids=["causal", "window"])
def test_chunked_grouped_attention_matches_repeated_kv(window):
    """Above ``CHUNK_THRESHOLD`` the prefill attends in q chunks over
    static K/V slices; with 4 query heads to a KV head each chunk is the
    grouped form, and the whole equals the repeated form over the whole
    sequence."""
    S = L.CHUNK_THRESHOLD + L.ATTN_CHUNK // 2
    q, k, v = _qkv(4, S, S, jnp.float32, b=1, hd=4)
    pos = jnp.arange(S)
    got = jax.jit(lambda q, k, v: L.gqa_attend_chunked(
        q, k, v, pos, pos, causal=True, window=window))(q, k, v)
    want = _reference(q, k, v, L._mask(pos, pos, True, window)[None, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-6)


# the device count has to be set before JAX starts, so the script runs in
# a process of its own
TP_SCRIPT = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh
from repro.models import layers as L
from repro.sharding.specs import default_rules, use_sharding

devices = jax.devices("cpu")
assert len(devices) == 4, devices
mesh = Mesh(np.array(devices).reshape(1, 4), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
H, KV, S = %(heads)d, %(kv)d, 16
kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(3), 3)
q = jax.random.normal(kq, (2, 1, H, 8), jnp.float32)
k = jax.random.normal(kk, (2, S, KV, 8), jnp.float32)
v = jax.random.normal(kv_, (2, S, KV, 8), jnp.float32)
mask = (jnp.arange(S) <= 11)[None, None, None, :]
one = jax.jit(L.gqa_attend)(q, k, v, mask)
rules = default_rules()
if KV %% 4:
    rules = rules.with_(kv_heads=None)


def sharded(q, k, v, mask):
    with use_sharding(mesh, rules):
        return L.gqa_attend(q, k, v, mask)


fn = jax.jit(sharded)
got = fn(q, k, v, mask)
text = fn.lower(q, k, v, mask).compile().as_text()
print(json.dumps({
    "gap": float(np.max(np.abs(np.asarray(got) - np.asarray(one)))),
    "gathers": [line.strip()[:160] for line in text.splitlines()
                if " all-gather(" in line or " all-gather-start(" in line]}))
"""


@pytest.mark.parametrize("heads,kv", [(32, 4), (12, 2)],
                         ids=["kv_heads_split", "kv_heads_whole"])
def test_grouped_attention_sharded_matches_one_device(heads, kv):
    """Yi-9B's 32 query and 4 KV heads split 8 and 1 a device; Qwen2-VL's
    12 query and 2 KV heads, whose KV heads do not divide the model axis,
    split 3 and none.  Either way each device scores its own query heads,
    the output equals one device's, and nothing is gathered."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(root / "src"))
    p = subprocess.run([sys.executable, "-c",
                        TP_SCRIPT % {"heads": heads, "kv": kv}],
                       cwd=root, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["gap"] <= 1e-6, got
    assert not got["gathers"], got["gathers"]

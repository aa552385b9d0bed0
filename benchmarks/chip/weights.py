"""Weights and token streams from the seed, made by the benchmark.

The dense family's weights are drawn in one jitted call on the device, in
the type they are served in, in the benchmark's own layout (every block's
leaf stacked over the layers), straight into the shardings they are used
in: no chip holds more than its share of a leaf, the float32 draw
included.  Threefry is partitionable (JAX's default), so the values do not
depend on the sharding.  :func:`to_program` arranges them into the
program's parameter tree; the reference draws them again from the same
seed, so it takes nothing the program has made.
"""
from __future__ import annotations

import math
from functools import partial

NORM_STD = 0.1          # norm scales: small random offsets from 1


def dense_shapes(c: dict) -> dict:
    """Leaf name -> shape, for a dense configuration ``c``."""
    L, D, V = c["n_layers"], c["d_model"], c["vocab"]
    H, KV, hd, F = c["n_heads"], c["n_kv_heads"], c["head_dim"], c["d_ff"]
    return {
        "embed": (V, D),
        "ln1": (L, D), "wq": (L, D, H * hd), "wk": (L, D, KV * hd),
        "wv": (L, D, KV * hd), "wo": (L, H * hd, D),
        "ln2": (L, D), "wg": (L, D, F), "wu": (L, D, F), "wd": (L, F, D),
        "final_norm": (D,), "unembed": (D, V),
    }


def _std(name, shape):
    if name in ("ln1", "ln2", "final_norm"):
        return NORM_STD
    # embed rows are scaled by sqrt(d_model) in the model, so std
    # 1/sqrt(d_model) gives a residual of unit scale, as each block adds;
    # matrices use 1/sqrt(fan_in)
    if name == "embed":
        return 1.0 / math.sqrt(shape[-1])
    return 1.0 / math.sqrt(shape[-2])


def _draw(shapes, dtype, key):
    import jax
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        out[name] = (jax.random.normal(k, shape, jax.numpy.float32)
                     * _std(name, shape)).astype(dtype)
    return out


def dense_weights(c: dict, seed: int, dtype="bfloat16", where=None):
    """Every weight of ``c`` from ``seed``, in one jitted call.

    ``where`` places the leaves: a device for all of them, or a dict of
    shardings keyed like :func:`dense_shapes`; by default JAX's default
    device."""
    import jax
    import jax.numpy as jnp
    from harness import seed31
    shapes = dense_shapes(c)
    if isinstance(where, jax.Device):
        where = jax.sharding.SingleDeviceSharding(where)
    fn = jax.jit(partial(_draw, shapes, jnp.dtype(dtype)),
                 out_shardings=where)
    return fn(jax.random.PRNGKey(seed31(seed)))


def spread(shapes: dict, devices) -> dict:
    """The reference's own placement of ``shapes`` over ``devices``: each
    leaf split over all of them along its last axis that they divide
    (never the first axis of a stacked leaf), or copied whole to each."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devices), ("d",))
    n = len(devices)
    out = {}
    for k, shape in shapes.items():
        axes = [a for a in range(len(shape) - 1, -1, -1)
                if shape[a] % n == 0 and (a > 0 or len(shape) == 1)]
        spec = [None] * len(shape)
        if axes and n > 1:
            spec[axes[0]] = "d"
        out[k] = NamedSharding(mesh, P(*spec))
    return out


def to_program(w: dict) -> dict:
    """The dense family's parameter tree of the program, one pattern
    group (``p0``) scanned over the layers."""
    return {
        "embed": w["embed"], "unembed": w["unembed"],
        "final_norm": w["final_norm"],
        "blocks": {"p0": {
            "pre_attn": w["ln1"], "pre_mlp": w["ln2"],
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: w[k] for k in ("wg", "wu", "wd")},
        }},
    }


def from_program(p: dict) -> dict:
    """Inverse of :func:`to_program`."""
    b = p["blocks"]["p0"]
    w = {"embed": p["embed"], "unembed": p["unembed"],
         "final_norm": p["final_norm"], "ln1": b["pre_attn"],
         "ln2": b["pre_mlp"]}
    w.update(b["attn"])
    w.update(b["mlp"])
    return w


def token_stream(seed: int, stream: int, shape, vocab: int):
    """Uniform token ids of ``shape`` from ``seed``, on the device; each
    ``stream`` number gives an independent draw."""
    import jax
    import jax.numpy as jnp
    from harness import seed31
    key = jax.random.fold_in(jax.random.PRNGKey(seed31(seed)),
                             1_000_003 + stream)
    return jax.jit(lambda k: jax.random.randint(
        k, tuple(shape), 0, vocab, jnp.int32))(key)

"""The chip's compiler without the chip: the Pallas kernels and the
full-width GPT train step compile for a described TPU v5e.

Nothing runs here, so these tests say nothing about results or times
(``python chip_smoke.py`` on a chip does).  They catch what interpret mode
cannot: a kernel the chip's compiler refuses (tiling, VMEM) or a step that
does not fit the chip's memory.  The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
every test worker imports this file.
"""
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels.rmsnorm import rmsnorm
from repro.models import registry
from repro.optim import adamw
from repro.train.loop import TrainConfig, make_train_step

HBM_BYTES = 16 * 2**30           # one TPU v5e chip

# the kernels phase of chip_smoke.py: GPT's attention and norm shapes
FLASH_SHAPE = (8, 1024, 12, 64)
RMSNORM_SHAPE = (8192, 768)
TRAIN_BATCH, TRAIN_SEQ = 8, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_flash(one_chip, shape, dtype):
    x = _on(one_chip, shape, dtype)
    return jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, causal=True)) \
        .lower(x, x, x).compile()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_compiles_to_kernel(one_chip, dtype):
    compiled = _compile_flash(one_chip, FLASH_SHAPE, dtype)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_rmsnorm_compiles_to_kernel(one_chip, dtype):
    x = _on(one_chip, RMSNORM_SHAPE, dtype)
    s = _on(one_chip, RMSNORM_SHAPE[-1:], dtype)
    compiled = jax.jit(lambda x, s: rmsnorm(x, s)).lower(x, s).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_vmem_bound(one_chip, dtype, monkeypatch):
    """The longest sequence the wrapper accepts compiles; at twice that
    the wrapper refuses it, and with the guard lifted so does the
    compiler, for want of VMEM."""
    S = fa.max_seq_len(128, dtype)
    assert S >= 4096
    compiled = _compile_flash(one_chip, (1, S, 8, 128), dtype)
    assert "tpu_custom_call" in compiled.as_text()
    too_long = (1, 2 * S, 8, 128)
    with pytest.raises(ValueError, match="VMEM"):
        _compile_flash(one_chip, too_long, dtype)
    monkeypatch.setattr(fa, "KV_BLOCK_MAX_BYTES", 2**30)
    with pytest.raises(Exception, match="vmem"):
        _compile_flash(one_chip, too_long, dtype)


def test_gpt_train_step_fits_one_chip(one_chip):
    """The bf16 GPT train step of chip_smoke.py at full width compiles
    for one chip and its buffers fit the chip's HBM.  Layer remat, as in
    the sharded step builder, is what makes it fit: without it the saved
    (B, H, S, S) attention scores take the step past 16 GiB."""
    cfg = replace(registry.load_config("gpt"), remat=True)

    def placed(tree):
        return jax.tree.map(lambda a: _on(one_chip, a.shape, a.dtype), tree)

    params = placed(registry.abstract_params(cfg))
    opt = placed(jax.eval_shape(adamw.init, params))
    tokens = _on(one_chip, (TRAIN_BATCH, TRAIN_SEQ), jnp.int32)
    batch = {"tokens": tokens, "labels": tokens}
    step = jax.jit(make_train_step(cfg, TrainConfig()), donate_argnums=(0, 1))
    mem = step.lower(params, opt, batch).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total


def test_dense_decode_updates_cache_in_place(one_chip):
    """Yi-9B's decode step at full width, two layers deep, with the cache
    donated: the layer loop writes each token into the stacked cache where
    it lies.  No whole stack is copied into the loop or out of it, and the
    step's scratch stays under one stack."""
    cfg = replace(registry.load_config("yi-9b"), n_layers=2)

    def placed(tree):
        return jax.tree.map(lambda a: _on(one_chip, a.shape, a.dtype), tree)

    params = placed(registry.abstract_params(cfg))
    cache = placed(registry.init_cache(cfg, 32, 4096, abstract=True))
    compiled = jax.jit(
        lambda p, c, t, s: registry.decode_step(p, cfg, c, t, s),
        donate_argnums=(1,)).lower(
            params, cache, _on(one_chip, (32, 1), jnp.int32),
            _on(one_chip, (), jnp.int32)).compile()
    stack = cache["p0"][0]
    shape = "bf16[" + ",".join(map(str, stack.shape)) + "]"
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    assert not [line for line in entry.splitlines()
                if f"= {shape}" in line and " copy(" in line]
    mem = compiled.memory_analysis()
    stack_bytes = stack.size * stack.dtype.itemsize
    assert mem.alias_size_in_bytes == 2 * stack_bytes
    assert mem.temp_size_in_bytes < stack_bytes, mem.temp_size_in_bytes

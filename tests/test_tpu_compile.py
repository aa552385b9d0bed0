"""The chip's compiler without the chip: the Pallas kernels and the
full-width GPT train step compile for a described TPU v5e.

Nothing runs here, so these tests say nothing about results or times
(``python chip_smoke.py`` on a chip does).  They catch what interpret mode
cannot: a kernel the chip's compiler refuses (tiling, VMEM) or a step that
does not fit the chip's memory.  The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
every test worker imports this file.
"""
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels.rmsnorm import rmsnorm
from repro.launch.mesh import rules_for_config
from repro.launch.steps import build_decode
from repro.models import registry
from repro.models.config import InputShape
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


_ATTEND_PRODUCT = re.compile(r'op_name="[^"]*/attend/[^"/]*/dot_general"')


def _computations(text):
    """``{name: body}`` of each computation of a compiled module's text."""
    return dict(re.findall(r"(?m)^(?:ENTRY )?%?([\w.\-]+) [^\n]*\{\n(.*?)\n\}",
                           text, re.S))


def test_dense_decode_attends_on_the_mxu(one_chip):
    """Yi-9B's greedy decode step at full width, two layers deep: its 32
    query heads attend in groups of 8 over each of the 4 KV heads.  Both
    of ``attend``'s products (scores and weighted sum) are convolutions
    inside ``kOutput`` fusions, the MXU's, and none is a multiply-reduce;
    no K/V is repeated to the 32 query heads (no ``bf16[32,4096,32]``
    product of the 32 sequences, 4096 positions and 32 heads)."""
    cfg = replace(registry.load_config("yi-9b"), n_layers=2)

    def placed(tree):
        return jax.tree.map(lambda a: _on(one_chip, a.shape, a.dtype), tree)

    def greedy(p, c, t, s):
        logits, c = registry.decode_step(p, cfg, c, t, s)
        return jnp.argmax(logits[:, 0], -1).astype(jnp.int32)[:, None], c

    text = jax.jit(greedy, donate_argnums=(1,)).lower(
        placed(registry.abstract_params(cfg)),
        placed(registry.init_cache(cfg, 32, 4096, abstract=True)),
        _on(one_chip, (32, 1), jnp.int32),
        _on(one_chip, (), jnp.int32)).compile().as_text()
    assert "bf16[32,4096,32]" not in text
    products = [line for line in text.splitlines()
                if _ATTEND_PRODUCT.search(line)]
    opcodes = {re.search(r"\s([a-z][\w\-]*)\(", line.split(" = ", 1)[1])
               .group(1) for line in products}
    assert "convolution" in opcodes and "reduce" not in opcodes, opcodes
    bodies = _computations(text)
    kinds = {}
    for line in text.splitlines():
        m = re.search(r" fusion\(.*kind=(k\w+), calls=%([\w.\-]+)", line)
        if m and any(" convolution(" in l and _ATTEND_PRODUCT.search(l)
                     for l in bodies.get(m.group(2), "").splitlines()):
            kinds[line.split(" = ")[0].strip()] = m.group(1)
    assert len(kinds) >= 2 and set(kinds.values()) == {"kOutput"}, kinds


def _tp4(topo, name="yi-9b", **reduced):
    """A model's steps from `build_decode` on a (1, 4) mesh of the v5e 2x2:
    Yi-9B whole unless named."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = replace(registry.load_config(name), **reduced)
    fn, abstract, shardings, donate = build_decode(
        cfg, InputShape("tp4", 4096, 32, "decode"), mesh,
        rules_for_config(cfg, mesh))
    args = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)
    return cfg, fn, args, shardings, donate


def _stack_moves(compiled):
    """The all-gathers and copies whose result is a K/V stack of Yi-9B's
    48 layers and 32 sequences, whole or one chip's shard."""
    return [line.strip()[:200] for line in compiled.as_text().splitlines()
            if " = " in line
            and line.split(" = ", 1)[1].lstrip("(").startswith(
                "bf16[48,32,")
            and re.search(r" (all-gather|copy)(-start)?\(", line)]


def test_yi9b_tensor_parallel_decode_keeps_the_cache_split(topo):
    """Yi-9B whole, all 48 layers at published widths, greedy decode of 32
    sequences into a 4096-position cache on a (1, 4) mesh of the v5e 2x2,
    through ``launch/steps.build_decode`` with the cache donated, as
    ``benchmarks/chip/drivers/decode.py`` builds it (all 48 layers: the
    compile takes seconds here).  Each chip holds one of the four KV heads: no K/V stack
    is gathered or copied, the step fits a chip, and the returned cache is
    still split by KV head."""
    cfg, fn, args, shardings, donate = _tp4(topo)

    def greedy(p, cache, tok, pos):
        logits, cache = fn(p, cache, tok, pos)
        return jnp.argmax(logits[:, 0], -1).astype(jnp.int32)[:, None], cache

    compiled = jax.jit(greedy, in_shardings=shardings,
                       donate_argnums=donate).lower(*args).compile()
    stack = args[1]["p0"][0]
    assert stack.shape == (48, 32, 4096, 4, 128)
    assert not _stack_moves(compiled)
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
    shard = stack.size * stack.dtype.itemsize // 4
    assert mem.alias_size_in_bytes == 2 * shard
    for out in jax.tree.leaves(compiled.output_shardings[1]):
        assert out.is_equivalent_to(shardings[1]["p0"][0], stack.ndim)


def test_yi9b_tensor_parallel_prefill_gathers_no_stack(topo):
    """The same cell's prefill in ``drivers/decode.py``:
    ``sequential_prefill`` of 512 prompt tokens into a 512-position cache,
    jitted with the cache's shardings outside ``use_sharding``.  No pin can learn the
    shard there, so none holds on four chips: no stack is gathered onto
    every chip in each layer of each step, and the prefill fits a chip."""
    from repro.train.serve import sequential_prefill
    cfg, _, args, shardings, _ = _tp4(topo)
    tokens = jax.ShapeDtypeStruct((32, 512), jnp.int32,
                                  sharding=shardings[2])
    compiled = jax.jit(
        lambda p, t: sequential_prefill(p, cfg, t, 512)[0],
        out_shardings=shardings[1]).lower(args[0], tokens).compile()
    assert not [m for m in _stack_moves(compiled) if "all-gather" in m]
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < HBM_BYTES, total


def test_grouped_decode_gathers_no_more_where_kv_heads_stay_whole(topo):
    """Qwen2-VL-2B at published widths, two layers deep, greedy decode of
    32 sequences into a 4096-position cache on the (1, 4) mesh: its 12
    query heads split 3 a chip, its 2 KV heads do not divide the model axis
    and stay whole on every chip.  The step gathers no more than the 8
    all-gathers of the step that repeated K/V to every query head (a
    compile of that step for the same described v5e 2x2: 6 of a K/V stack
    in the layer loop, 2 for the argmax), and no K/V stack at all."""
    cfg, fn, args, shardings, donate = _tp4(topo, "qwen2-vl-2b", n_layers=2)

    def greedy(p, cache, tok, pos):
        logits, cache = fn(p, cache, tok, pos)
        return jnp.argmax(logits[:, 0], -1).astype(jnp.int32)[:, None], cache

    text = jax.jit(greedy, in_shardings=shardings,
                   donate_argnums=donate).lower(*args).compile().as_text()
    gathers = [line.strip()[:200] for line in text.splitlines()
               if re.search(r" all-gather(-start)?\(", line)]
    assert len(gathers) <= 8, gathers
    stack = "bf16[" + ",".join(map(str, args[1]["p0"][0].shape[:2]))
    assert not [g for g in gathers
                if g.split(" = ", 1)[1].lstrip("(").startswith(stack)], \
        gathers

"""Substrate tests: data pipeline determinism, checkpoint round-trip,
optimizer behaviour, loss decreases on a tiny model."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, restore_checkpoint, save_checkpoint
from repro.data.pipeline import SyntheticTextDataset
from repro.launch import compile_cache
from repro.launch import train as train_launcher
from repro.models import registry
from repro.optim import adamw
from repro.optim.adamw import AdamWConfig
from repro.train.loop import TrainConfig, make_train_step


def test_pipeline_deterministic_and_sharded():
    a = SyntheticTextDataset(vocab=100, seq_len=16, batch=4, seed=7)
    b = SyntheticTextDataset(vocab=100, seq_len=16, batch=4, seed=7)
    np.testing.assert_array_equal(a.batch_at(3)["tokens"],
                                  b.batch_at(3)["tokens"])
    s0 = SyntheticTextDataset(vocab=100, seq_len=16, batch=4, seed=7,
                              n_shards=2, shard=0)
    s1 = SyntheticTextDataset(vocab=100, seq_len=16, batch=4, seed=7,
                              n_shards=2, shard=1)
    assert not np.array_equal(s0.batch_at(0)["tokens"],
                              s1.batch_at(0)["tokens"])


def test_checkpoint_roundtrip():
    tree = {"a": np.arange(6, dtype=np.float32).reshape(2, 3),
            "b": {"c": (np.ones(3, np.int32), np.zeros(2))}}
    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(d, 12, tree)
        assert latest_step(d) == 12
        step, back = restore_checkpoint(d, 12, tree)
    assert step == 12
    np.testing.assert_array_equal(back["a"], tree["a"])
    np.testing.assert_array_equal(back["b"]["c"][0], tree["b"]["c"][0])


def test_adamw_moves_params_toward_gradient():
    params = {"w": jnp.ones((4,))}
    state = adamw.init(params)
    grads = {"w": jnp.ones((4,))}
    cfg = AdamWConfig(lr=0.1, warmup_steps=1, weight_decay=0.0)
    new, state, gnorm = adamw.update(grads, state, params, cfg)
    assert float(gnorm) > 0
    assert np.all(np.asarray(new["w"]) < 1.0)


def test_loss_decreases_tiny_gpt():
    cfg = registry.load_config("gpt").reduced()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw.init(params)
    step = jax.jit(make_train_step(
        cfg, TrainConfig(optimizer=AdamWConfig(lr=3e-3, warmup_steps=5))))
    ds = SyntheticTextDataset(vocab=cfg.vocab, seq_len=32, batch=4)
    losses = []
    for i in range(30):
        params, opt, m = step(params, opt, ds.batch_at(i))
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]), losses


def test_grad_accum_matches_full_batch():
    """Microbatched grads == full-batch grads (the verified property)."""
    cfg = registry.load_config("gpt").reduced()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    ds = SyntheticTextDataset(vocab=cfg.vocab, seq_len=16, batch=4)
    batch = ds.batch_at(0)
    o1 = adamw.init(params)
    o2 = adamw.init(params)
    s1 = jax.jit(make_train_step(cfg, TrainConfig(microbatches=1)))
    s2 = jax.jit(make_train_step(cfg, TrainConfig(microbatches=2)))
    p1, _, m1 = s1(params, o1, batch)
    p2, _, m2 = s2(params, o2, batch)
    l1 = jax.tree.leaves(p1)
    l2 = jax.tree.leaves(p2)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-3)


@pytest.fixture
def cache_dir_config():
    """Restore JAX's compile-cache directory after the test."""
    old = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", old)


def test_compile_cache_defers_to_env(monkeypatch, cache_dir_config):
    monkeypatch.setenv(compile_cache.ENV_DIR, "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo(monkeypatch, cache_dir_config):
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_train_launcher_runs_in_process(monkeypatch, capsys):
    monkeypatch.setattr(train_launcher, "enable_compile_cache", lambda: None)
    train_launcher.main(["--arch", "gpt", "--steps", "1", "--batch", "2",
                         "--seq", "16"])
    loss = float(capsys.readouterr().out.split()[-1])
    assert np.isfinite(loss)

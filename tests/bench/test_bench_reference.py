"""The plain float32 reference of the dense family against the program's
model code, at a tiny size on the CPU."""

import numpy as np
import pytest

from conftest import TINY_DENSE, harness


@pytest.fixture(scope="module")
def tiny32():
    import jax
    import weights as W
    c = dict(TINY_DENSE, dtype="float32")
    cfg = harness.program_config(c)
    w = W.dense_weights(c, 2**31 + 3, "float32", jax.devices("cpu")[0])
    return c, cfg, w


def test_forward_matches_program(tiny32):
    import jax
    import jax.numpy as jnp
    import reference as R
    import weights as W
    from repro.models import registry
    c, cfg, w = tiny32
    tokens = W.token_stream(5, 0, (2, 24), c["vocab"])
    with jax.default_matmul_precision("highest"):
        got, _ = registry.forward(W.to_program(w), cfg, {"tokens": tokens})
        want = R.head(c, w, R.forward_hidden(c, w, tokens))
    assert harness.rel_err(got, want) < 1e-5
    # the layer-by-layer path gives the same hidden state
    x = R.hidden_layerwise(c, w, tokens)
    with jax.default_matmul_precision("highest"):
        assert harness.rel_err(R.head(c, w, x), want) < 1e-5
    assert float(jnp.std(want)) > 0.1


def test_training_matches_program(tiny32):
    """Three AdamW steps of the reference against the program's step."""
    import jax
    import reference as R
    import weights as W
    from repro.train.loop import TrainConfig, make_train_step
    c, cfg, w = tiny32
    o = TrainConfig().optimizer
    opt = (o.lr, o.b1, o.b2, o.eps, o.weight_decay, o.clip_norm,
           o.warmup_steps)
    pool = W.token_stream(9, 0, (3, 2, 17), c["vocab"])
    batches = [(pool[i, :, :-1], pool[i, :, 1:]) for i in range(3)]
    losses, g, w3 = R.train(c, opt, w, batches, 3, 1)
    step = jax.jit(make_train_step(cfg, TrainConfig()))
    params = W.to_program(w)
    state = {"mu": jax.tree.map(np.zeros_like, params),
             "nu": jax.tree.map(np.zeros_like, params),
             "step": np.zeros((), np.int32)}
    with jax.default_matmul_precision("highest"):
        for i, (t, l) in enumerate(batches):
            params, state, m = step(params, state, {"tokens": t, "labels": l})
            assert abs(float(m["loss"]) - losses[i]) < 1e-5 * losses[i]
    for k, v in W.from_program(params).items():
        moved = float(np.linalg.norm(np.asarray(w3[k]) - np.asarray(w[k])))
        gap = float(np.linalg.norm(np.asarray(v) - np.asarray(w3[k])))
        assert gap <= 1e-3 * max(moved, 1e-12), k


def test_served_gap_is_zero_for_reference_greedy(tiny32):
    """Tokens that the reference itself puts first read a gap of 0, and a
    token chosen elsewhere reads more."""
    import jax
    import jax.numpy as jnp
    import reference as R
    import weights as W
    c, _, w = tiny32
    tokens = W.token_stream(3, 0, (1, 16), c["vocab"])
    with jax.default_matmul_precision("highest"):
        best = jnp.argmax(R.head(c, w, R.forward_hidden(c, w, tokens)), -1)
    assert float(jnp.max(R.served_gaps(c, w, tokens, best))) == 0.0
    other = (best + 1) % c["vocab"]
    assert float(jnp.min(R.served_gaps(c, w, tokens, other))) > 0.0


def test_reference_on_given_devices_is_bit_for_bit(tiny32):
    """Rows placed on a list of devices, and blocks that do not fill the
    last one, give the gaps of the default placement exactly."""
    import jax
    import numpy as np
    import reference as R
    import weights as W
    c, _, w = tiny32
    tokens = np.asarray(W.token_stream(4, 0, (3, 16), c["vocab"]))
    served = np.asarray(W.token_stream(4, 1, (3, 16), c["vocab"]))
    cpu = jax.devices("cpu")[:1]
    want = np.asarray(R.served_gaps(c, w, tokens, served))
    for rows in (1, 2):
        got = R.served_gaps(c, w, tokens, served, rows, devices=cpu)
        assert np.array_equal(np.asarray(got), want)
    want = np.asarray(R.control_gaps(c, w, tokens))
    got = R.control_gaps(c, w, tokens, 2, devices=cpu)
    assert np.array_equal(np.asarray(got), want)

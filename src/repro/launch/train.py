"""Training launcher: build the model and optimizer and run the training loop.

    PYTHONPATH=src python -m repro.launch.train --arch gpt --steps 100

Without ``--full`` it trains the reduced (smoke-test) variant of the
architecture, which runs on the CPU; ``--full`` trains the published
configuration, and one TPU v5e chip holds GPT at full width.
"""
import argparse

import jax

from ..data.pipeline import SyntheticTextDataset
from ..models import registry
from ..optim import adamw
from ..train.loop import TrainConfig, make_train_step
from ..checkpoint import save_checkpoint
from .compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--full", action="store_true",
                    help="use the published config (one v5e chip holds GPT)")
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()
    cfg = registry.load_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    params = registry.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw.init(params)
    step_fn = jax.jit(make_train_step(cfg, TrainConfig()),
                      donate_argnums=(0, 1))
    ds = SyntheticTextDataset(vocab=cfg.vocab, seq_len=args.seq,
                              batch=args.batch)
    for step in range(args.steps):
        params, opt, m = step_fn(params, opt, ds.batch_at(step))
        if step % 10 == 0:
            print(f"step {step} loss {float(m['loss']):.4f}")
    if args.ckpt:
        save_checkpoint(args.ckpt, args.steps, {"params": params})


if __name__ == "__main__":
    main()

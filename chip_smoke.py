"""Bring-up smoke: drive GraphGuard's main path once on one TPU chip.

    python chip_smoke.py              # one chip: verify, train, serve, kernels
    python chip_smoke.py --chips 4    # four chips: the sharded GPT train step
                                      # and the same step on one of them

Every phase runs in this one process, the only one that opens the chip;
the verifier's pool workers are pinned to the CPU backend.

* verify   the verifier CLI in-process, cold (no certificate cache): GPT
           under dp2xtp2 certifies; a wrong spec injected at layer 3 fails
           block 4 alone; the dp_accum train step certifies on a 2-worker
           spawn pool started while this process holds the chip.
* train    GPT at its published width (12 x 768, vocab 50257, bf16): 5
           AdamW steps at batch 8 x seq 1024, each loss finite; then a
           float32 forward on the chip against the same forward on the
           host CPU backend.
* serve    float32 parallel prefill against KV-cache decode of the same
           prompts; then 4 bf16 prompts of 128 tokens, 32 greedy tokens
           each.
* kernels  the Pallas flash-attention and RMSNorm kernels compiled for the
           chip (not interpreted) against ``kernels/ref.py``.

With ``--chips 4`` only the sharded train step runs: GPT in float32 on a
2x2 ``(data, model)`` mesh against the same step on one chip.

Each phase prints a ``phase {...}`` JSON line: wall and compile seconds,
every check with its error and tolerance, and the device's
``peak_bytes_in_use``.  The last line is ``{"ok": true, "device": {...}}``.
A failed check, or no TPU, exits non-zero without that line.  Weights are
random, made from ``--seed``.  The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``.
"""
import argparse
import contextlib
import io
import json
import math
import os
import sys
import time
from dataclasses import replace
from functools import partial

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

TOL = 1e-3                     # float32 checks, relative to max |reference|
# tests/test_kernels.py's tolerances (atol = rtol) per dtype
FLASH_TOL = {"bfloat16": 5e-2, "float32": 2e-4}
RMSNORM_TOL = {"bfloat16": 3e-2, "float32": 1e-5}

TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 5
REF_BATCH, REF_SEQ = 2, 128
N_REQUESTS, PROMPT_LEN, GEN_TOKENS = 4, 128, 32
FLASH_SHAPE = (8, 1024, 12, 64)         # GPT's attention at seq 1024
RMSNORM_SHAPE = (8192, 768)             # GPT's residual, batch 8 x 1024
SHARDED_BATCH, SHARDED_SEQ = 8, 512


class SmokeFailure(Exception):
    """A check of the smoke failed."""


def peak_bytes(devices):
    """Largest ``peak_bytes_in_use`` over ``devices`` (None if unknown)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def rel_err(got, want) -> float:
    """max |got - want| over max |want|, in float64."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


class Phase:
    """Times one phase, collects its checks and prints its record on exit,
    failed or not."""

    def __init__(self, name, devices):
        self.name = name
        self.devices = devices
        self.rec = {"phase": name, "compile_s": 0.0, "checks": []}

    def __enter__(self):
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.rec["wall_s"] = time.perf_counter() - self.t0
        self.rec["peak_bytes_in_use"] = peak_bytes(self.devices)
        self.rec["ok"] = exc_type is None
        print("phase " + json.dumps(self.rec), flush=True)
        return False

    @contextlib.contextmanager
    def timed(self, what):
        """Add the block's seconds to ``{what}_s``."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            key = f"{what}_s"
            self.rec[key] = self.rec.get(key, 0.0) + time.perf_counter() - t0

    def check(self, name, ok, **info):
        self.rec["checks"].append({"check": name, "ok": bool(ok), **info})
        if not ok:
            raise SmokeFailure(f"{self.name}: {name}: {info}")

    def close_to(self, name, got, want, tol=TOL):
        err = rel_err(got, want)
        self.check(name, err <= tol, rel_err=err, tol=tol)

    def allclose(self, name, got, want, tol):
        """``np.allclose`` with atol = rtol = tol, as the kernel tests."""
        import numpy as np
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        self.check(name, np.allclose(got, want, atol=tol, rtol=tol),
                   max_abs_err=float(np.max(np.abs(got - want))), tol=tol)


# ---------------------------------------------------------------------------
# verify: the checker's CLI, in this process
# ---------------------------------------------------------------------------

def run_cli(argv):
    """``repro.launch.verify.main(argv)`` -> (exit code, stdout, seconds)."""
    from repro.launch.verify import main
    out = io.StringIO()
    t0 = time.perf_counter()
    with contextlib.redirect_stdout(out):
        try:
            main(argv)
            rc = 0
        except SystemExit as e:
            rc = e.code if isinstance(e.code, int) else (e.code is not None)
    return int(rc), out.getvalue(), time.perf_counter() - t0


def _envelope_report(out):
    try:
        return json.loads(out)["report"]
    except (ValueError, KeyError):
        return None


def phase_verify(ph):
    rc, out, wall = run_cli(["--model", "gpt", "--plan", "dp2xtp2",
                             "--no-cache"])
    ph.check("gpt@dp2xtp2 certifies",
             rc == 0 and "WHOLE-MODEL REFINEMENT HOLDS" in out,
             rc=rc, wall_s=wall)

    rc, out, wall = run_cli(["--model", "gpt", "--plan", "dp2xtp2",
                             "--inject-bug", "wrong_spec", "--bug-layer", "3",
                             "--json", "--no-cache"])
    rep = _envelope_report(out) or {}
    ph.check("wrong_spec at layer 3 fails block 4 alone",
             rc == 1 and rep.get("failing_blocks") == [4],
             rc=rc, failing_blocks=rep.get("failing_blocks"), wall_s=wall)

    rc, out, wall = run_cli(["--train", "dp_accum", "--workers", "2",
                             "--json", "--no-cache"])
    rep = _envelope_report(out) or {}
    nested = rep.get("reports", {})
    runtime = {p: r.get("runtime") or {} for p, r in nested.items()}
    clean_pool = all("degraded_reason" not in info
                     and info.get("attempts", 1) == 1
                     for info in runtime.values())
    ph.check("train@dp_accum certifies on a 2-worker spawn pool",
             rc == 0 and rep.get("verdict") == "certificate"
             and sorted(nested) == ["w1", "w2"] and rep.get("workers") == 2
             and clean_pool,
             rc=rc, params=sorted(nested), workers=rep.get("workers"),
             runtime=runtime, wall_s=wall)


# ---------------------------------------------------------------------------
# train / serve: the model substrate at GPT's published width
# ---------------------------------------------------------------------------

def _tokens(seed, cfg, batch, seq):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32)


def _init_params(ph, cfg, seed):
    """GPT's random weights from ``seed``, timed as ``init_s``."""
    import jax
    from repro.models import registry
    with ph.timed("init"):
        return jax.block_until_ready(
            registry.init_params(cfg, jax.random.PRNGKey(seed)))


def phase_train(ph, cfg, seed):
    """``launch/train.py --full``'s step: jit with donation, AdamW, the
    synthetic dataset; layer remat so batch 8 x 1024 fits 16 GB (see
    tests/test_tpu_compile.py)."""
    import jax
    from repro.data.pipeline import SyntheticTextDataset
    from repro.models import registry
    from repro.optim import adamw
    from repro.train.loop import TrainConfig, make_train_step

    cfg = replace(cfg, remat=True)
    params = _init_params(ph, cfg, seed)
    opt = adamw.init(params)
    ds = SyntheticTextDataset(vocab=cfg.vocab, seq_len=TRAIN_SEQ,
                              batch=TRAIN_BATCH, seed=seed)
    step_fn = jax.jit(make_train_step(cfg, TrainConfig()),
                      donate_argnums=(0, 1))
    with ph.timed("compile"):
        step = step_fn.lower(params, opt, ds.batch_at(0)).compile()
    losses = []
    t0 = time.perf_counter()
    for i in range(TRAIN_STEPS):
        params, opt, metrics = step(params, opt, ds.batch_at(i))
        losses.append(float(metrics["loss"]))
    ph.rec["steps_s"] = time.perf_counter() - t0
    ph.check(f"bf16 loss finite at all {TRAIN_STEPS} steps",
             all(math.isfinite(x) for x in losses), losses=losses)
    del params, opt

    cfg32 = replace(cfg, dtype="float32", remat=False)
    params = _init_params(ph, cfg32, seed)
    tokens = _tokens(seed, cfg32, REF_BATCH, REF_SEQ)
    cpu = jax.devices("cpu")[0]
    params_cpu, tokens_cpu = jax.device_put((params, tokens), cpu)

    def forward(p, t):
        return registry.forward(p, cfg32, {"tokens": t})[0]

    with jax.default_matmul_precision("highest"), ph.timed("compile"):
        on_chip = jax.jit(forward).lower(params, tokens).compile()
        on_cpu = jax.jit(forward).lower(params_cpu, tokens_cpu).compile()
    with ph.timed("cpu_reference"):
        want = jax.block_until_ready(on_cpu(params_cpu, tokens_cpu))
    ph.close_to("float32 logits: chip vs host CPU",
                on_chip(params, tokens), want)


def phase_serve(ph, cfg, seed):
    import jax
    import jax.numpy as jnp
    from repro.train.serve import (decode_tokens, prefill_logits,
                                   sequential_prefill)

    cfg32 = replace(cfg, dtype="float32")
    params = _init_params(ph, cfg32, seed)
    tokens = _tokens(seed, cfg32, REF_BATCH, REF_SEQ)
    with jax.default_matmul_precision("highest"), ph.timed("compile"):
        parallel = jax.jit(
            lambda p, t: prefill_logits(p, cfg32, {"tokens": t})
        ).lower(params, tokens).compile()
        stepwise = jax.jit(
            lambda p, t: sequential_prefill(p, cfg32, t, REF_SEQ)[1]
        ).lower(params, tokens).compile()
    ph.close_to("float32 prefill vs KV-cache decode",
                stepwise(params, tokens), parallel(params, tokens))
    del params

    params = _init_params(ph, cfg, seed)
    prompts = _tokens(seed + 1, cfg, N_REQUESTS, PROMPT_LEN)

    def answer(p, prompts):
        cache, logits = sequential_prefill(p, cfg, prompts,
                                           PROMPT_LEN + GEN_TOKENS)
        first = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
        _, rest = decode_tokens(p, cfg, cache, first, PROMPT_LEN,
                                GEN_TOKENS - 1)
        return jnp.concatenate([first, rest], axis=1)

    with ph.timed("compile"):
        serve = jax.jit(answer).lower(params, prompts).compile()
    t0 = time.perf_counter()
    out = jax.device_get(serve(params, prompts))
    ph.rec["requests_s"] = time.perf_counter() - t0
    ph.check(f"{N_REQUESTS} requests x {GEN_TOKENS} greedy tokens "
             f"in [0, vocab)",
             out.shape == (N_REQUESTS, GEN_TOKENS)
             and bool((out >= 0).all() and (out < cfg.vocab).all()),
             shape=list(out.shape), first_tokens=out[:, :4].tolist())


# ---------------------------------------------------------------------------
# kernels: Pallas compiled for the chip
# ---------------------------------------------------------------------------

def phase_kernels(ph, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rmsnorm import rmsnorm

    rng = np.random.default_rng(seed)
    cases = []
    for dtype in ("bfloat16", "float32"):
        q, k, v = (jnp.asarray(rng.normal(size=FLASH_SHAPE), dtype)
                   for _ in range(3))
        cases.append((f"flash_attention {dtype} causal",
                      partial(flash_attention, causal=True, interpret=False),
                      partial(ref.flash_attention_ref, causal=True),
                      (q, k, v), FLASH_TOL[dtype]))
    for dtype in ("bfloat16", "float32"):
        x = jnp.asarray(rng.normal(size=RMSNORM_SHAPE), dtype)
        s = jnp.asarray(rng.normal(size=RMSNORM_SHAPE[-1:]) * 0.1, dtype)
        cases.append((f"rmsnorm {dtype}",
                      partial(rmsnorm, interpret=False), ref.rmsnorm_ref,
                      (x, s), RMSNORM_TOL[dtype]))
    for name, kernel, oracle, args, tol in cases:
        lowered = jax.jit(kernel).lower(*args)
        ph.check(f"{name} lowers to a TPU kernel",
                 "tpu_custom_call" in lowered.as_text())
        with ph.timed("compile"):
            compiled = lowered.compile()
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        ph.allclose(f"{name} vs kernels/ref.py", compiled(*args), want, tol)


# ---------------------------------------------------------------------------
# --chips 4: the sharded train step against one chip
# ---------------------------------------------------------------------------

def phase_sharded(ph, cfg, seed, devices):
    """``launch/steps.build_train`` on a 2x2 (data, model) mesh and on a
    1x1 mesh of its first chip, from the same float32 state and batch."""
    import jax
    import numpy as np
    from jax.sharding import AxisType, Mesh
    from repro.data.pipeline import SyntheticTextDataset
    from repro.launch.mesh import rules_for_config
    from repro.launch.steps import build_train
    from repro.models.config import InputShape
    from repro.optim import adamw

    cfg32 = replace(cfg, dtype="float32")
    shape = InputShape("chip_smoke", SHARDED_SEQ, SHARDED_BATCH, "train")
    params = jax.device_get(_init_params(ph, cfg32, seed))
    state = (params, jax.device_get(adamw.init(params)),
             jax.device_get(SyntheticTextDataset(
                 vocab=cfg32.vocab, seq_len=SHARDED_SEQ,
                 batch=SHARDED_BATCH, seed=seed).batch_at(0)))
    meshes = {"2x2": np.array(devices[:4]).reshape(2, 2),
              "1x1": np.array(devices[:1]).reshape(1, 1)}
    results = {}
    for name, devs in meshes.items():
        mesh = Mesh(devs, ("data", "model"),
                    axis_types=(AxisType.Auto, AxisType.Auto))
        fn, _, shardings, donate = build_train(
            cfg32, shape, mesh, rules_for_config(cfg32, mesh))
        args = jax.device_put(state, shardings)
        step = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
        with jax.default_matmul_precision("highest"), ph.timed("compile"):
            compiled = step.lower(*args).compile()
        t0 = time.perf_counter()
        results[name] = jax.block_until_ready(compiled(*args))
        ph.rec[f"step_s_{name}"] = time.perf_counter() - t0

    new_params, new_opt, metrics = results["2x2"]
    spans = {jax.tree_util.keystr(path): len(leaf.sharding.device_set)
             for path, leaf in jax.tree_util.tree_leaves_with_path(
                 new_params)}
    ph.check("updated parameters span the 4 chips",
             set(spans.values()) == {4}, device_set_sizes=spans)
    ref_params, ref_opt, ref_metrics = results["1x1"]
    for key in ("loss", "grad_norm"):
        ph.close_to(f"{key}: 2x2 mesh vs one chip",
                    metrics[key], ref_metrics[key])
    for label, got, want in (("params", new_params, ref_params),
                             ("adam mu", new_opt["mu"], ref_opt["mu"])):
        errs = {jax.tree_util.keystr(path): rel_err(g, w)
                for (path, g), w in zip(
                    jax.tree_util.tree_leaves_with_path(got),
                    jax.tree.leaves(want))}
        worst = max(errs, key=errs.get)
        ph.check(f"updated {label}, every leaf: 2x2 mesh vs one chip",
                 errs[worst] <= TOL, worst_leaf=worst,
                 rel_err=errs[worst], tol=TOL)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Drive GraphGuard's main path once on a TPU.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on a 2x2 mesh "
                         "and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: the repro package is not at {SRC}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found: {e}", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found: JAX's devices are "
              f"{devices[0].platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"chips, JAX sees {len(devices)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import registry
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print("device " + json.dumps(dict(
        device, jax=jax.__version__, compile_cache=enable_compile_cache())),
        flush=True)
    cfg = registry.load_config("gpt")
    if args.chips == 4:
        phases = [("sharded_train",
                   lambda ph: phase_sharded(ph, cfg, args.seed, devices))]
    else:
        phases = [("verify", phase_verify),
                  ("train", lambda ph: phase_train(ph, cfg, args.seed)),
                  ("serve", lambda ph: phase_serve(ph, cfg, args.seed)),
                  ("kernels", lambda ph: phase_kernels(ph, args.seed))]
    used = devices[:args.chips]
    wall = compile_s = 0.0
    for name, run in phases:
        try:
            with Phase(name, used) as ph:
                run(ph)
        except SmokeFailure as e:
            print(f"chip_smoke: FAIL {e}", file=sys.stderr)
            return 1
        wall += ph.rec["wall_s"]
        compile_s += ph.rec["compile_s"]
    print("summary " + json.dumps({"wall_s": wall, "compile_s": compile_s}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

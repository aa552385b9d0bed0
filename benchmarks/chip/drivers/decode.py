"""Greedy decoding of a batch of prompts, a token per sequence a step.

Set-up draws the weights (with the configuration's family, straight into
the program's parameter shardings on the mix's mesh) and the prompts from
the seed, fills the KV cache with the program's
``train/serve.sequential_prefill`` (its last logits give each sequence's
first token; the positions past the prompt hold zeros, as the program's
``init_cache`` makes them) and compiles the program's decode step from
``launch/steps.build_decode`` with the greedy choice fed back on the
device.  The window runs steps until ``seconds`` have passed, reading each
step's tokens back as a server streams them.  Where the cache would
overflow, positions rewind to the prompt's end: a new answer to the same
prompt.

Once the window has closed and the program's state is freed, the plain
float32 reference runs over a sample of the sequences, drawn from the seed:
each prompt with its first answer.  It draws the weights again, spread
over the cell's chips, and runs layer by layer, each layer gathered whole
onto every chip and the sampled rows split over the chips.
``served_gap`` is the widest gap by which a served token's reference logit
lies below the reference's best.
"""
from __future__ import annotations

import tempfile
import time

import numpy as np

import harness
from harness import Check, Run

HOST_SPANS = ("window", "dispatch", "readback")


def build(cell, devices, seed: int, wrap=None):
    """Weights, prompts, the filled cache and the compiled greedy step."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import rules_for_config
    from repro.launch.steps import build_decode
    from repro.models.config import InputShape
    from repro.train.serve import sequential_prefill
    import weights as W

    c, t = cell.config, cell.traffic
    F = harness.family(c["family"])
    B, P, M = t["batch"], t["prompt"], t["max_seq"]
    cfg = harness.program_config(c)
    mesh = harness.mesh_of(devices, t["mesh"])
    shape = InputShape(cell.name, M, B, "decode")
    fn, abstract, shardings, _ = build_decode(
        cfg, shape, mesh, rules_for_config(cfg, mesh))
    if wrap is not None:
        fn = wrap(fn)
    w = F.weights(c, seed, c["dtype"], F.from_program(shardings[0]))
    params = jax.jit(F.to_program, out_shardings=shardings[0],
                     donate_argnums=0)(w)
    del w
    prompts = W.token_stream(seed, 0, (B, P), c["vocab"])
    harness.mark("build")

    def prefill(p, tokens):
        # the program's prefill into a cache of the prompt's length, then
        # the rest of the 4096 positions as ``init_cache`` makes them
        # (zeros): each prefill step then moves a cache of 512 positions
        # and not of 4096
        cache, logits = sequential_prefill(p, cfg, tokens, P)
        cache = jax.tree.map(
            lambda a: jnp.pad(a, [(0, 0)] * 2 + [(0, M - P)]
                              + [(0, 0)] * (a.ndim - 3)), cache)
        return cache, jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]

    cache, first = jax.jit(prefill, out_shardings=shardings[1:3])(
        params, prompts)
    jax.block_until_ready(cache)
    harness.mark("prefill")

    def greedy(p, cache, tok, pos):
        logits, cache = fn(p, cache, tok, pos)
        return jnp.argmax(logits[:, 0], -1).astype(jnp.int32)[:, None], cache

    step = jax.jit(greedy, in_shardings=shardings, donate_argnums=(1,)) \
        .lower(params, cache, first, np.int32(P)).compile()
    harness.mark("compile")
    return step, params, cache, first, prompts


def run(cell, devices, *, seed: int, seconds: float, trace: bool,
        t0: float, wrap=None) -> Run:
    import jax
    t = cell.traffic
    B, P, M = t["batch"], t["prompt"], t["max_seq"]
    step, params, cache, first, prompts = build(cell, devices, seed, wrap)
    first_host = np.asarray(first)
    prompts_host = np.asarray(prompts)

    if trace:
        tdir = tempfile.mkdtemp(prefix="chip-trace-")
        jax.profiler.start_trace(tdir)
    answer = [first_host]            # the first answer, token by token
    positions, times = [], []
    tok, pos, rewound = first, P, 0
    start = time.perf_counter()
    setup_s = start - t0
    with jax.profiler.TraceAnnotation("window"):
        while True:
            s0 = time.perf_counter()
            if s0 - start >= seconds:
                break
            with jax.profiler.TraceAnnotation("dispatch"):
                tok, cache = step(params, cache, tok, np.int32(pos))
            with jax.profiler.TraceAnnotation("readback"):
                got = np.asarray(tok)
            times.append(time.perf_counter() - s0)
            positions.append(pos)
            if not rewound:
                answer.append(got)
            pos += 1
            if pos == M:                      # the cache is full
                pos, tok, rewound = P, first, rewound + 1
    window_s = time.perf_counter() - start
    peak = harness.peak_bytes(devices)
    tr = brk = None
    if trace:
        jax.profiler.stop_trace()
        from devtrace import extract, reduce
        tr = reduce(extract(tdir, HOST_SPANS), chips=len(devices))
        brk = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)
    del step, params, cache, tok, first

    served = np.concatenate(answer, 1)               # (B, n + 1)
    rows = sample_rows(seed, B, t["sample"])
    gap = served_gap(cell, seed, devices, prompts_host[rows], served[rows])
    n = len(times)
    return Run(cell=cell, setup_s=setup_s, window_s=window_s,
               attempted=n * B, failed=0,
               records={"steps": n, "tokens": n * B, "positions": positions,
                        "step_s": times,
                        "answer_len": served.shape[1], "rows": rows,
                        "served": served},
               checks=[Check("served_gap", gap,
                             float(cell.limits["served_gap"]))],
               trace=tr, breakdown=brk, peak_bytes=peak)


def sample_rows(seed: int, batch: int, k: int) -> list:
    rng = np.random.default_rng(seed)
    return sorted(int(r) for r in rng.choice(batch, size=min(k, batch),
                                             replace=False))


def reference_inputs(prompts, served, bucket: int):
    """The reference's input (prompt and all served tokens but the last,
    padded to a multiple of ``bucket``) and, per position, the served
    token that followed it (-1 where nothing was served)."""
    B, P = prompts.shape
    seq = np.concatenate([prompts, served[:, :-1]], 1)
    T = -(-seq.shape[1] // bucket) * bucket
    tokens = np.zeros((B, T), np.int32)
    tokens[:, :seq.shape[1]] = seq
    target = np.full((B, T), -1, np.int32)
    target[:, P - 1:P - 1 + served.shape[1]] = served
    return tokens, target


def served_gap(cell, seed: int, devices, prompts, served, fp8=False):
    """Widest reference gap of a served token (or, with ``fp8``, of the
    fp8 control's first choice at the same positions)."""
    import weights as W
    c, t = cell.config, cell.traffic
    F = harness.family(c["family"])
    tokens, target = reference_inputs(prompts, served, t["ref_bucket"])
    w = F.weights(c, seed, c["dtype"], W.spread(F.shapes(c), devices))
    if fp8:
        gaps = F.control_gaps(c, w, tokens, t["ref_rows"], devices)
    else:
        gaps = F.served_gaps(c, w, tokens, np.maximum(target, 0),
                             t["ref_rows"], devices)
    gaps = np.asarray(gaps)
    return float(np.max(gaps[target >= 0]))

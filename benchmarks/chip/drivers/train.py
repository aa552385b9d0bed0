"""Training steps of the program, back to back.

Set-up builds one object, the program's jitted train step from
``launch/steps.build_train`` with its state, on weights drawn from the seed,
and drives it through the mix's first ``checked_steps`` steps, each on its
own batch, through the same call the window uses.  The window then runs
steps on further batches of a pool drawn from the seed until ``seconds``
have passed, as a training loop does: the mix's ``ahead_s`` seconds of
steps are dispatched ahead of the one whose loss is read back, so that the
chip stays fed while the host stands still.  When the time is up nothing
more is sent, every step sent is waited for, and the clock is read after
that wait: all of those steps count, over all of that time.

Once the window has closed and the program's state is freed, the plain
float32 reference (``reference.py``) repeats the checked steps from the same
weights and batches, and three numbers are compared:

- ``loss_gap``: the largest relative gap of a checked step's loss;
- ``grad_gap``: the first gradient as the optimizer got it (Adam's first
  moment after step 1 over 1 - b1), by the worst leaf: the gap between the
  program's norm and the reference's, over the larger of the reference's
  norm of that leaf and of the median leaf;
- ``change_gap``: the same for the change of the parameters over the
  checked steps, as the next step receives them.

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of both leaf numbers: they move by round-off alone.
"""
from __future__ import annotations

import tempfile
import time
from collections import deque

import numpy as np

import harness
from harness import Check, Run

HOST_SPANS = ("window", "dispatch", "readback")


def build(cell, devices, seed: int, wrap=None):
    """The program's compiled train step and its first state."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import rules_for_config
    from repro.launch.steps import build_train
    from repro.models.config import InputShape
    from repro.train.loop import TrainConfig
    import weights as W

    c, t = cell.config, cell.traffic
    F = harness.family(c["family"])
    stated = t["optimizer"]
    got = TrainConfig().optimizer
    for k, v in stated.items():
        if getattr(got, k) != v:
            raise ValueError(f"the program's AdamW has {k}={getattr(got, k)}"
                             f", the mix states {v}")
    cfg = harness.program_config(c)
    mesh = harness.mesh_of(devices, t["mesh"])
    shape = InputShape(cell.name, t["seq"], t["batch"], "train")
    fn, abstract, shardings, donate = build_train(
        cfg, shape, mesh, rules_for_config(cfg, mesh))
    if wrap is not None:
        fn = wrap(fn)
    step = jax.jit(fn, in_shardings=shardings, donate_argnums=donate)
    w0 = F.weights(c, seed, c["dtype"], F.from_program(shardings[0]))
    params = jax.jit(F.to_program, out_shardings=shardings[0])(w0)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract[0])
    have = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != have:
        raise ValueError("the benchmark's weights do not match the "
                         "program's parameter tree")
    opt = jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), abstract[1]),
        out_shardings=shardings[1])()
    pool = W.token_stream(seed, 0, (t["pool"], t["batch"], t["seq"] + 1),
                          c["vocab"])
    split = jax.jit(lambda p: [{"tokens": p[i, :, :-1], "labels": p[i, :, 1:]}
                               for i in range(p.shape[0])],
                    out_shardings=[shardings[2]] * t["pool"])
    batches = split(pool)
    harness.mark("build")
    return step, params, opt, batches, w0


def _norms(tree):
    import jax.numpy as jnp
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def run(cell, devices, *, seed: int, seconds: float, trace: bool,
        t0: float, wrap=None) -> Run:
    import jax

    t = cell.traffic
    F = harness.family(cell.config["family"])
    b1 = t["optimizer"]["b1"]
    n_checked = t["checked_steps"]
    step, params, opt, batches, w0 = build(cell, devices, seed, wrap)
    grad_norms = jax.jit(lambda mu: _norms(
        {k: v / (1 - b1) for k, v in F.from_program(mu).items()}))
    change_norms = jax.jit(lambda p, p0: _norms(
        {k: v.astype("float32") - p0[k].astype("float32")
         for k, v in F.from_program(p).items()}))
    losses, g1 = [], None
    for i in range(n_checked):
        t_step = time.perf_counter()
        params, opt, m = step(params, opt, batches[i])
        losses.append(float(m["loss"]))
        t_step = time.perf_counter() - t_step
        if i == 0:
            g1 = {k: float(v) for k, v in grad_norms(opt["mu"]).items()}
    # steps in flight: the mix's lead over the last checked step's time
    depth = max(1, round(t.get("ahead_s", 0.0) / t_step))
    dp = {k: float(v) for k, v in change_norms(params, w0).items()}
    del w0
    harness.mark("checked_steps")

    if trace:
        tdir = tempfile.mkdtemp(prefix="chip-trace-")
        jax.profiler.start_trace(tdir)
    n, pool = 0, len(batches)
    inflight = deque()
    start = time.perf_counter()
    setup_s = start - t0
    with jax.profiler.TraceAnnotation("window"):
        while time.perf_counter() - start < seconds:
            with jax.profiler.TraceAnnotation("dispatch"):
                params, opt, m = step(params, opt,
                                      batches[(n_checked + n) % pool])
            inflight.append(m["loss"])
            n += 1
            if len(inflight) > depth:
                with jax.profiler.TraceAnnotation("readback"):
                    float(inflight.popleft())
        with jax.profiler.TraceAnnotation("readback"):
            jax.block_until_ready((params, opt, m))
    window_s = time.perf_counter() - start
    inflight.clear()
    last_loss = float(m["loss"]) if n else float("nan")
    peak = harness.peak_bytes(devices)
    tr = brk = None
    if trace:
        jax.profiler.stop_trace()
        from devtrace import extract, reduce
        tr = reduce(extract(tdir, HOST_SPANS), chips=len(devices))
        brk = {"device_ops": tr["device_ops"], "idle_gaps": tr["idle_gaps"]}
        import shutil
        shutil.rmtree(tdir, ignore_errors=True)
    del params, opt, m, batches, step

    ref = reference_readings(cell, seed, devices)
    readings = compare(losses, g1, dp, ref)
    checks = [Check(k, readings[k], float(cell.limits[k]))
              for k in ("loss_gap", "grad_gap", "change_gap")]
    tokens = n * t["batch"] * t["seq"]
    return Run(cell=cell, setup_s=setup_s, window_s=window_s,
               attempted=n, failed=0 if last_loss == last_loss else n,
               records={"steps": n, "tokens": tokens, "ahead_steps": depth,
                        "readings": readings},
               checks=checks, trace=tr, breakdown=brk, peak_bytes=peak)


def reference_readings(cell, seed: int, devices, fp8=False) -> dict:
    """The reference's losses, first-gradient and change norms per leaf."""
    import jax
    import jax.numpy as jnp
    import weights as W

    c, t = cell.config, cell.traffic
    F = harness.family(c["family"])
    o = t["optimizer"]
    opt = (o["lr"], o["b1"], o["b2"], o["eps"], o["weight_decay"],
           o["clip_norm"], o["warmup_steps"])
    w0 = F.weights(c, seed, c["dtype"], devices[0])
    pool = W.token_stream(seed, 0, (t["pool"], t["batch"], t["seq"] + 1),
                          c["vocab"])
    n = t["checked_steps"]
    batches = [(pool[i, :, :-1], pool[i, :, 1:]) for i in range(n)]
    losses, g, w3 = F.train(c, opt, w0, batches, n, t["ref_rows_per_block"],
                            fp8=fp8)
    norms = jax.jit(_norms)
    return {"losses": losses,
            "grad": {k: float(v) for k, v in norms(g).items()},
            "change": {k: float(v) for k, v in norms(
                {k: w3[k].astype(jnp.float32) - w0[k].astype(jnp.float32)
                 for k in w0}).items()}}


def compare(losses, g1: dict, dp: dict, ref: dict) -> dict:
    """The three numbers, and which leaves counted."""
    gmed = float(np.median(list(ref["grad"].values())))
    leaves = [k for k, v in ref["grad"].items() if v >= 1e-3 * gmed]
    cmed = float(np.median([ref["change"][k] for k in leaves]))

    def worst(got, want, med):
        gaps = {k: abs(got[k] - want[k]) / max(want[k], med, 1e-30)
                for k in leaves}
        k = max(gaps, key=gaps.get)
        return gaps[k], k
    grad_gap, grad_leaf = worst(g1, ref["grad"], gmed)
    change_gap, change_leaf = worst(dp, ref["change"], cmed)
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses,
                                                       ref["losses"]))
    return {"loss_gap": float(loss_gap), "grad_gap": float(grad_gap),
            "change_gap": float(change_gap), "grad_leaf": grad_leaf,
            "change_leaf": change_leaf,
            "left_out": sorted(set(ref["grad"]) - set(leaves)),
            "losses": list(losses), "ref_losses": list(ref["losses"])}

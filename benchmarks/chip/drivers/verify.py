"""Cold verdicts back to back, in process, as the verifier's CLI gives them.

The mix is a ``round`` of verdicts (a plan each, for a model check; one
entry per verdict for a serving check).  An entry with ``"layer":
"seed"`` injects its ``bug`` at a layer drawn from the seed, a new one each
round.  Set-up imports the verifier and runs ``warmup_rounds`` rounds; the
window then runs whole rounds until ``seconds`` have passed.  The
certificate cache is off and the workers are the verifier's default.

Each verdict is judged against what the deployment is known to be: a clean
one certifies with no failing block or step; one with a bug injected at
layer k is refuted with exactly block k + 1 failing.  The reference is
that table, made from the mix and the seed; nothing the verifier says
enters it.

The checker puts no work on the chip.  The traced run's profiled round,
after the window, runs one tiny operation on the device after each of its
verdicts, so that its trace has the device plane to show that it idled
(the profiler can miss an operation made just after it starts); the
window runs none.
"""
from __future__ import annotations

import tempfile
import time

import numpy as np

import harness
from harness import Check, Run

HOST_SPANS = ("window", "verdict")


def _checker(cell):
    chk = cell.config["check"]
    if chk["kind"] == "model":
        from repro.modelcheck import check_model

        def run(entry, engine_opts=None):
            return check_model(chk["target"], entry["plan"],
                               bug=entry.get("bug"),
                               bug_layer=entry.get("layer_k"),
                               engine_opts=engine_opts, cache=False)
        return run
    if chk["kind"] == "serve":
        from repro.servecheck import check_serve

        def run(entry, engine_opts=None):
            return check_serve(chk["target"], degree=chk.get("degree"),
                               bug=entry.get("bug"),
                               engine_opts=engine_opts, cache=False)
        return run
    raise ValueError(f"unknown check kind `{chk['kind']}`")


def rounds(cell, seed: int):
    """The mix's rounds, forever, with seeded layers filled in."""
    rng = np.random.default_rng(seed)
    n_layers = cell.config.get("n_layers", 0)
    while True:
        out = []
        for e in cell.traffic["round"]:
            e = dict(e)
            if e.get("layer") == "seed":
                e["layer_k"] = int(rng.integers(0, n_layers))
            out.append(e)
        yield out


def expected(entry) -> tuple:
    if entry.get("bug"):
        return ("refinement_error", [entry["layer_k"] + 1])
    return ("certificate", [])


def outcome(report) -> dict:
    failing = getattr(report, "failing_blocks", None)
    if failing is None:
        failing = getattr(report, "failing_steps", [])
    timing = report.timing()
    fires = sum(sum(((r.get("stats") or {}).get("lemma_fires") or {})
                    .values()) for r in report.reports.values())
    return {"verdict": report.verdict, "failing": list(failing),
            "wall_s": timing["wall_s"], "infer_s": timing["infer_s_sum"],
            "phase_s": timing["phase_s_sum"], "fires": fires}


def judge(records) -> int:
    """Verdicts that disagree with the table."""
    return sum(1 for r in records
               if (r["outcome"]["verdict"], r["outcome"]["failing"])
               != tuple(r["expected"]))


def run(cell, devices, *, seed: int, seconds: float, trace: bool,
        t0: float, wrap=None, engine_opts=None) -> Run:
    import jax
    import jax.numpy as jnp
    check = _checker(cell)
    if wrap is not None:
        check = wrap(check)
    if trace:
        tick = jax.jit(lambda x: x + 1.0)
        x = jax.device_put(jnp.zeros((8, 128), jnp.float32), devices[0])
        x = tick(x).block_until_ready()
    harness.mark("checker")
    gen = rounds(cell, seed)
    for _ in range(cell.traffic.get("warmup_rounds", 1)):
        for e in next(gen):
            check(e, engine_opts)
    harness.mark("warmup")
    records = []
    start = time.perf_counter()
    setup_s = start - t0

    def verdicts(into, after=None):
        for e in next(gen):
            with jax.profiler.TraceAnnotation("verdict"):
                try:
                    o = outcome(check(e, engine_opts))
                except Exception as err:     # a verdict that never came
                    o = {"verdict": f"raised {type(err).__name__}",
                         "failing": [], "error": str(err)}
            into.append({"entry": e, "expected": list(expected(e)),
                         "outcome": o})
            if after is not None:
                after()

    while True:
        verdicts(records)
        if time.perf_counter() - start >= seconds:
            break
    window_s = time.perf_counter() - start
    peak = harness.peak_bytes(devices)
    tr = brk = None
    traced = []                      # verdicts of the rounds after the window
    if trace:
        # The profiler's host tracer slows the checker several times over
        # (capture makes many JAX traces), and so does the checker's own
        # tracer, which times every lemma call.  The window above runs
        # with neither; one more round under the profiler gives the
        # device's busy and idle time, and one under the checker's tracer
        # the breakdown.  Their verdicts are judged like the window's.
        tdir = tempfile.mkdtemp(prefix="chip-trace-")
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation("window"):
            verdicts(traced, after=lambda: tick(x).block_until_ready())
        jax.profiler.stop_trace()
        from devtrace import extract, reduce
        tr = reduce(extract(tdir, HOST_SPANS))
        _rmtree(tdir)
        from repro.obs import trace as obs_trace
        tracer = obs_trace.start("bench")
        verdicts(traced)
        obs_trace.stop()
        brk = breakdown(tr, tracer.events)
    wrong = judge(records + traced)
    return Run(cell=cell, setup_s=setup_s, window_s=window_s,
               attempted=len(records) + len(traced), failed=wrong,
               records={"verdicts": records},
               checks=[Check("verdicts_wrong", float(wrong),
                             float(cell.limits["verdicts_wrong"]))],
               trace=tr, breakdown=brk, peak_bytes=peak)


def self_times(events) -> dict:
    """Seconds of each span name net of the spans nested inside it, per
    thread (``X`` events of the checker's own tracer)."""
    out = {}
    by_tid = {}
    for e in events:
        if e.get("ph") == "X":
            by_tid.setdefault((e.get("pid"), e.get("tid")), []).append(e)
    for evs in by_tid.values():
        evs.sort(key=lambda e: (e["ts"], -e["dur"]))
        stack = []              # [end_us, name, child_us, dur_us]
        for e in evs + [None]:
            while stack and (e is None or e["ts"] >= stack[-1][0]):
                end, name, child, dur = stack.pop()
                out[name] = out.get(name, 0.0) + (dur - child) * 1e-6
                if stack:
                    stack[-1][2] += dur
            if e is not None:
                stack.append([e["ts"] + e["dur"], e["name"], 0.0, e["dur"]])
    return out


def breakdown(tr: dict, events) -> dict:
    """Device ops as traced; the host's time while the device idled, by
    the checker's spans (self time) and by lemma (in-lemma time)."""
    from repro.obs.inspect import lemma_totals
    host = self_times(events)
    host.update({f"lemma:{k}": v["ms"] * 1e-3
                 for k, v in lemma_totals(events).items()})
    top = sorted(host.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": tr["device_ops"],
            "idle_gaps": [[k, v] for k, v in top]}


def _rmtree(path):
    import shutil
    shutil.rmtree(path, ignore_errors=True)

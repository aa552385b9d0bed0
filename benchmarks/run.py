"""Benchmark harness — one section per paper table/figure.

  fig4      end-to-end verification time per model/strategy   (paper Fig. 4)
  fig5      scaling vs parallelism degree                     (paper Fig. 5)
  fam_scaling  FSDP / pipeline / 2D-mesh family scaling with
            degree (incl. per-axis tuple degrees)
  gradcheck training-step verification per train strategy
            (repro.gradcheck per-parameter gradient obligations)
  suite     repro.api.Suite process-pool runner vs sequential
            run_case looping on the clean degree-2 matrix
  runtime   persistent certificate cache: cold vs warm whole-model
            re-verification (repro.runtime.cache)
  ablation  sp_moe deg 8: optimized engine vs the same commit
            with dispatch/extraction optimizations disabled
  fig6      lemma-library effort: count + complexity          (paper Fig. 6)
  fig7      lemma application counts per case                 (paper Fig. 7)

Prints ``name,us_per_call,derived`` CSV rows (derived = e-graph nodes or
counts, per section) and writes machine-readable ``BENCH_verify.json``
(per-case wall/infer time, e-graph nodes, lemma fires, proof-provenance
chain steps, per-phase timers; warmup + median-of-N repeats) so the perf
trajectory is tracked across PRs.

    python benchmarks/run.py [--smoke] [--repeats N] [--json PATH]

A section that raises leaves an ``ERROR`` row and the run exits 1.
"""
import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))

REPEATS = 3


def _cases():
    from repro.api import verify
    return verify


def _sum_explain_steps(reports):
    """Total proof-provenance chain steps across a scheduler's unique
    obligations (from one untimed explain-on run).

    Chain reconstruction canonicalizes over the term quotient, so the
    count is byte-stable per section and scripts/check_bench.py gates it
    with exact equality — a changed count means the proofs themselves
    changed shape, not that the machine was slow."""
    from repro.core.explain import explanation_steps
    return sum(explanation_steps(rep.get("explanation"))
               for rep in reports.values())


def _sum_lemma_fires(reports):
    """Total lemma fires across a scheduler's unique obligations.

    Saturation is deterministic, so this is byte-stable per section and
    scripts/check_bench.py gates it with exact equality — a changed count
    means the engine did different work, not that the machine was slow."""
    total = 0
    for rep in reports.values():
        fires = (rep.get("stats") or {}).get("lemma_fires") or {}
        total += sum(fires.values())
    return total


def _timed_case(verify, case, degree=2, repeats=None):
    """Warmup once, then median-of-N: returns a JSON-ready record.

    wall_ms includes jax tracing + SPMD expansion (constant per case);
    infer_ms is the relation-inference time the engine work targets.
    Raises if the verdict misses the registry expectation, so a silently
    broken strategy fails the section instead of timing garbage.
    """
    repeats = repeats or REPEATS

    def checked(r):
        assert r.verdict == "certificate", \
            f"{case}@deg{degree}: verdict {r.verdict} " \
            f"(expected {r.expected}) — " \
            f"{r.error or (r.localization or {}).get('op_name')}"
        return r

    checked(verify(case, degree=degree))           # warmup
    walls, infers = [], []
    report = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        report = checked(verify(case, degree=degree))
        walls.append((time.perf_counter() - t0) * 1e3)
        infers.append(report.stats["time_s"] * 1e3)
    # one extra untimed explain-on run: provenance chain length is a
    # determinism signal (gated exactly), not a timing
    from repro.core.explain import explanation_steps
    xrep = checked(verify(case, degree=degree,
                          engine_opts={"explain": True}))
    stats = report.stats
    return {
        "wall_ms": round(statistics.median(walls), 3),
        "infer_ms": round(statistics.median(infers), 3),
        "egraph_nodes": stats["egraph_nodes"],
        "gs_ops": stats["gs_ops"],
        "gd_ops": stats["gd_ops"],
        "lemma_fires": sum(stats["lemma_fires"].values()),
        "explain_steps": explanation_steps(xrep.explanation),
        "phase_ms": {k: round(v * 1e3, 3)
                     for k, v in stats["phase_s"].items()},
        "counters": stats["counters"],
    }


def fig4_verification_time(rows, out, repeats=None):
    """Per-case end-to-end verification time (paper Fig. 4 analogue).
    The paper's models map onto these strategy cases: GPT/Megatron -> TP+SP,
    Qwen2/vLLM -> TP, Llama-3/Neuron -> TP, HF regression -> grad-accum;
    the weight-sharded / pipeline / 2D-mesh families (fsdp_mlp, pp_stage,
    tp_dp_2d) cover the bug-study strategies beyond the paper's case set."""
    verify = _cases()
    sec = out.setdefault("fig4", {})
    for case in ["tp_layer", "sp_pad", "ep_moe", "sp_moe", "ln_grad",
                 "sp_rope", "fsdp_mlp", "pp_stage", "tp_dp_2d"]:
        rec = _timed_case(verify, case, repeats=repeats)
        sec[case] = rec
        rows.append((f"fig4/{case}", rec["wall_ms"] * 1e3,
                     rec["egraph_nodes"]))


def fig5_scaling(rows, out, repeats=None):
    """Verification time vs parallelism degree (2, 4, 8)."""
    verify = _cases()
    sec = out.setdefault("fig5", {})
    for deg in (2, 4, 8):
        rec = _timed_case(verify, "sp_moe", degree=deg, repeats=repeats)
        sec[f"sp_moe_deg{deg}"] = rec
        rows.append((f"fig5/sp_moe_deg{deg}", rec["wall_ms"] * 1e3,
                     rec["egraph_nodes"]))
    for deg in (2, 4):
        try:
            rec = _timed_case(verify, "tp_layer", degree=deg,
                              repeats=repeats)
            nodes = rec["egraph_nodes"]
        except Exception as e:   # completeness gap at this degree — record it
            rec = {"error": type(e).__name__}
            nodes = -1
        sec[f"tp_layer_deg{deg}"] = rec
        rows.append((f"fig5/tp_layer_deg{deg}",
                     rec.get("wall_ms", 0.0) * 1e3, nodes))


def fam_scaling(rows, out, repeats=None):
    """Scaling of the weight-sharded / pipeline / 2D-mesh families with
    degree (per mesh axis for tp_dp_2d) — including the two former scale
    limits the n-ary add normal form closed: ``fsdp_mlp@8`` (was ~21 s of
    assoc/comm tax, now seconds) and the 16-rank ``tp_dp_2d@(4,4)`` (used
    to blow up saturation and false-alarm, now milliseconds)."""
    from repro.api import degree_token
    verify = _cases()
    sec = out.setdefault("fam_scaling", {})
    for case, degrees in [("fsdp_mlp", (2, 4, 8)), ("pp_stage", (2, 4)),
                          ("tp_dp_2d", ((2, 2), (4, 2), (4, 4)))]:
        for deg in degrees:
            rec = _timed_case(verify, case, degree=deg, repeats=repeats)
            key = f"{case}_deg{degree_token(deg)}"
            sec[key] = rec
            rows.append((f"fam_scaling/{key}", rec["wall_ms"] * 1e3,
                         rec["egraph_nodes"]))


def modelcheck_bench(rows, out, repeats=None):
    """Whole-model verification (repro.modelcheck): wall/infer time plus
    unique-obligations vs total-blocks (the dedup ratio is the scale
    story — e.g. kimi's 63 blocks cost 3 verifications).  The case list is
    identical in smoke and full runs so the bench gate
    (scripts/check_bench.py) can require every baseline case."""
    import statistics as _st

    from repro.modelcheck import check_model
    repeats = repeats or REPEATS
    sec = out.setdefault("modelcheck", {})
    cases = [("gpt", "dp2xtp2"), ("gpt", "dp2"),
             ("gemma3-12b", "dp2xtp2"), ("mixtral-8x7b", "tp2")]
    for model, plan in cases:
        def one():
            rep = check_model(model, plan, workers=0)
            assert rep.verdict == "certificate", \
                f"{model}@{plan}: {rep.verdict} (blocks {rep.failing_blocks})"
            return rep
        one()                                          # warmup
        walls, infers, rep = [], [], None
        for _ in range(repeats):
            t0 = time.perf_counter()
            rep = one()
            walls.append((time.perf_counter() - t0) * 1e3)
            infers.append(rep.timing()["infer_s_sum"] * 1e3)
        xrep = check_model(model, plan, workers=0,
                           engine_opts={"explain": True})
        key = f"{model}@{plan}"
        sec[key] = {
            "wall_ms": round(_st.median(walls), 3),
            "infer_ms": round(_st.median(infers), 3),
            "total_blocks": rep.total_blocks,
            "unique_obligations": rep.unique_obligations,
            "dedup_ratio": rep.dedup_ratio,
            "lemma_fires": _sum_lemma_fires(rep.reports),
            "explain_steps": _sum_explain_steps(xrep.reports),
        }
        rows.append((f"modelcheck/{key}", sec[key]["wall_ms"] * 1e3,
                     rep.unique_obligations))


def gradcheck_bench(rows, out, repeats=None):
    """Training-step verification (repro.gradcheck): wall/infer time per
    train strategy — the per-parameter gradient obligations with the
    transposition seam check.  The case list is identical in smoke and
    full runs so the bench gate (scripts/check_bench.py) can require
    every baseline case."""
    import statistics as _st

    from repro.gradcheck import check_train
    repeats = repeats or REPEATS
    sec = out.setdefault("gradcheck", {})
    cases = [("dp", 2), ("dp_accum", 2), ("fsdp", 2), ("tp_dp_2d", (4, 4))]
    for strategy, degree in cases:
        def one():
            rep = check_train(strategy, degree=degree, workers=0)
            assert rep.verdict == "certificate", \
                f"train@{strategy}: {rep.verdict} ({rep.failing_params})"
            return rep
        one()                                          # warmup
        walls, infers, rep = [], [], None
        for _ in range(repeats):
            t0 = time.perf_counter()
            rep = one()
            walls.append((time.perf_counter() - t0) * 1e3)
            infers.append(rep.timing()["infer_s_sum"] * 1e3)
        from repro.api import degree_token
        xrep = check_train(strategy, degree=degree, workers=0,
                           engine_opts={"explain": True})
        key = f"train@{strategy}@deg{degree_token(degree)}"
        sec[key] = {
            "wall_ms": round(_st.median(walls), 3),
            "infer_ms": round(_st.median(infers), 3),
            "params": len(rep.params),
            "lemma_fires": _sum_lemma_fires(rep.reports),
            "explain_steps": _sum_explain_steps(xrep.reports),
        }
        rows.append((f"gradcheck/{key}", sec[key]["wall_ms"] * 1e3,
                     len(rep.params)))


def servecheck_bench(rows, out, repeats=None):
    """Serving-path verification (repro.servecheck): wall/infer time per
    serve strategy — decode-step obligations deduped by position class
    plus the prefill-read chain.  sp_cache is excluded from the timed set
    (its read obligation is ~17 s at degree 2 — tier-1 tests cover it);
    the case list is identical in smoke and full runs so the bench gate
    (scripts/check_bench.py) can require every baseline case."""
    import statistics as _st

    from repro.servecheck import check_serve
    repeats = repeats or REPEATS
    sec = out.setdefault("servecheck", {})
    cases = [("tp_decode", 2), ("batched_decode", (2, 2))]
    for strategy, degree in cases:
        def one():
            rep = check_serve(strategy, degree=degree, workers=0)
            assert rep.verdict == "certificate", \
                f"serve@{strategy}: {rep.verdict} ({rep.failing_steps})"
            return rep
        one()                                          # warmup
        walls, infers, rep = [], [], None
        for _ in range(repeats):
            t0 = time.perf_counter()
            rep = one()
            walls.append((time.perf_counter() - t0) * 1e3)
            infers.append(rep.timing()["infer_s_sum"] * 1e3)
        from repro.api import degree_token
        xrep = check_serve(strategy, degree=degree, workers=0,
                           engine_opts={"explain": True})
        key = f"serve@{strategy}@deg{degree_token(degree)}"
        sec[key] = {
            "wall_ms": round(_st.median(walls), 3),
            "infer_ms": round(_st.median(infers), 3),
            "total_steps": rep.total_steps,
            "unique_obligations": rep.unique_obligations,
            "dedup_ratio": rep.dedup_ratio,
            "lemma_fires": _sum_lemma_fires(rep.reports),
            "explain_steps": _sum_explain_steps(xrep.reports),
        }
        rows.append((f"servecheck/{key}", sec[key]["wall_ms"] * 1e3,
                     rep.unique_obligations))


def suite_runner(rows, out, repeats=None):
    """Suite process-pool runner vs sequential run_case looping.

    Both modes sweep the clean degree-2 matrix (every registered case,
    bug=None).  Sequential = ``Suite.run(workers=0)``, i.e. exactly the
    in-process run_case loop the CLI used to do; parallel = 4 pool
    workers with the warmed, persistent pool (steady state — the first
    parallel sweep, which additionally pays pool spin-up + per-worker
    jax backend init, is reported as ``first_parallel_run_ms``).
    Median + min of N interleaved-ish repeats; the
    section asserts the two modes' stable summaries (verdicts + R_o
    certificates) are identical before reporting any numbers.
    """
    from repro.api import Suite

    # the container CPU is very noisy and each sweep is ~100 ms, so take
    # the min over a larger interleaved sample than the other sections
    repeats = max(repeats or REPEATS, 9)
    with Suite(degrees=(2,)) as suite:
        n_tasks = len(suite.tasks())
        res_seq = suite.run(workers=0)             # warmup sequential
        t0 = time.perf_counter()
        res_par = suite.run(workers=4)             # pool + backend init
        first_par_s = time.perf_counter() - t0
        assert res_seq.stable_summary() == res_par.stable_summary(), \
            "suite results differ between sequential and pool execution"
        seqs, pars = [], []
        for _ in range(repeats):
            seqs.append(suite.run(workers=0).wall_s)
            pars.append(suite.run(workers=4).wall_s)
    seq_ms = min(seqs) * 1e3
    par_ms = min(pars) * 1e3
    out["suite"] = {
        "tasks": n_tasks,
        "workers": 4,
        "sequential_ms": round(seq_ms, 3),
        "workers4_ms": round(par_ms, 3),
        "sequential_ms_median": round(statistics.median(seqs) * 1e3, 3),
        "workers4_ms_median": round(statistics.median(pars) * 1e3, 3),
        "first_parallel_run_ms": round(first_par_s * 1e3, 3),
        "speedup": round(seq_ms / par_ms, 2),
        "results_identical": True,
    }
    rows.append(("suite/clean_deg2/sequential", seq_ms * 1e3, n_tasks))
    rows.append(("suite/clean_deg2/workers4", par_ms * 1e3, n_tasks))
    rows.append(("suite/clean_deg2/speedup_x100", 0.0,
                 int(100 * seq_ms / par_ms)))


def runtime_bench(rows, out, repeats=None):
    """Persistent certificate cache (repro.runtime.cache): cold vs warm
    whole-model re-verification of gpt@dp2xtp2.  The warm number is the
    latency of re-verifying an unchanged model from the journal — the
    pre-launch hot path the cache exists for — and is gated by
    scripts/check_bench.py.  Each repeat uses a fresh cache directory so
    colds stay cold; asserts the warm run is all hits before timing
    counts."""
    import shutil
    import statistics as _st
    import tempfile

    from repro.modelcheck import check_model
    repeats = repeats or REPEATS
    sec = out.setdefault("runtime", {})
    colds, warms, hits = [], [], 0
    for _ in range(repeats):
        d = tempfile.mkdtemp(prefix="graphguard-bench-cache-")
        try:
            t0 = time.perf_counter()
            cold = check_model("gpt", "dp2xtp2", workers=0, cache=d)
            colds.append((time.perf_counter() - t0) * 1e3)
            assert cold.verdict == "certificate" \
                and cold.cache["hits"] == 0, \
                f"cold run not clean: {cold.verdict}, {cold.cache}"
            t0 = time.perf_counter()
            warm = check_model("gpt", "dp2xtp2", workers=0, cache=d)
            warms.append((time.perf_counter() - t0) * 1e3)
            assert warm.cache["misses"] == 0, \
                f"warm run missed the cache: {warm.cache}"
            assert cold.stable_summary() == warm.stable_summary(), \
                "warm certificates differ from cold"
            hits = warm.cache["hits"]
        finally:
            shutil.rmtree(d, ignore_errors=True)
    cold_ms, warm_ms = _st.median(colds), _st.median(warms)
    sec["gpt@dp2xtp2"] = {
        "cold_wall_ms": round(cold_ms, 3),
        "warm_wall_ms": round(warm_ms, 3),
        "obligations": hits,
        "speedup": round(cold_ms / max(warm_ms, 1e-9), 2),
        "results_identical": True,
    }
    rows.append(("runtime/gpt@dp2xtp2/cold", cold_ms * 1e3, hits))
    rows.append(("runtime/gpt@dp2xtp2/warm", warm_ms * 1e3, hits))


def ablation_engine(rows, out, repeats=None):
    """sp_moe at degree 8: optimized engine vs the un-optimized baseline
    (op-indexed dispatch, deferred rebuild, incremental extraction, indexed
    frontier, cached node sets — all toggled together) on the same commit."""
    from repro.core import capture, capture_spmd, check_refinement, expand_spmd
    from repro.core.profile import CONFIG, set_optimizations
    from repro.dist import strategies as S

    saved_flags = CONFIG.as_dict()

    repeats = max(repeats or REPEATS, 5)
    seq_fn, dist_fn, axes, specs, avals, names = S.sp_moe_layer(degree=8)
    gs = capture(seq_fn, avals, names)
    cap = capture_spmd(dist_fn, axes, specs, avals, names)
    gd, r_i = expand_spmd(cap)

    def one(flag):
        set_optimizations(flag)
        cert = check_refinement(gs, gd, r_i)
        return cert.stats["time_s"] * 1e3, cert

    # interleave optimized/baseline runs and take the per-mode minimum so a
    # noisy-neighbour CPU spike cannot land entirely on one mode
    try:
        one(True)
        one(False)                                 # warmup both modes
        opts, bases = [], []
        for _ in range(repeats):
            t, cert_on = one(True)
            opts.append(t)
            t, cert_off = one(False)
            bases.append(t)
    finally:
        # restore whatever mode the process was launched in (GRAPHGUARD_OPT)
        set_optimizations(True, **saved_flags)
    opt_ms, base_ms = min(opts), min(bases)
    assert cert_on.r_o == cert_off.r_o, \
        "optimizations changed the certificate — behaviour not preserved!"
    out["ablation"] = {
        "case": "sp_moe_deg8",
        "optimized_infer_ms": round(opt_ms, 3),
        "baseline_infer_ms": round(base_ms, 3),
        "optimized_infer_ms_median": round(statistics.median(opts), 3),
        "baseline_infer_ms_median": round(statistics.median(bases), 3),
        "speedup": round(base_ms / opt_ms, 2),
        "certificates_identical": True,
    }
    rows.append(("ablation/sp_moe_deg8/optimized", opt_ms * 1e3,
                 cert_on.stats["egraph_nodes"]))
    rows.append(("ablation/sp_moe_deg8/baseline", base_ms * 1e3,
                 cert_off.stats["egraph_nodes"]))
    rows.append(("ablation/sp_moe_deg8/speedup_x100",
                 0.0, int(100 * base_ms / opt_ms)))


def fig6_lemma_effort(rows, out):
    """Lemma library size + complexity (paper Fig. 6: effort to add)."""
    from repro.core.lemmas import all_lemmas
    lemmas = all_lemmas()
    import inspect
    sec = out.setdefault("fig6", {"loc": {}, "source": {}})
    total_loc = 0
    for lem in lemmas:
        loc = len(inspect.getsource(lem.fn).splitlines())
        total_loc += loc
        sec["loc"][lem.name] = loc
        rows.append((f"fig6/loc/{lem.name}", 0.0, loc))
    sec["n_lemmas"] = len(lemmas)
    sec["avg_loc"] = total_loc // max(len(lemmas), 1)
    rows.append(("fig6/n_lemmas", 0.0, len(lemmas)))
    rows.append(("fig6/avg_loc", 0.0, sec["avg_loc"]))
    by_src = {}
    for lem in lemmas:
        by_src[lem.source] = by_src.get(lem.source, 0) + 1
    for src, n in sorted(by_src.items()):
        sec["source"][src] = n
        rows.append((f"fig6/source/{src}", 0.0, n))


def fig7_lemma_heatmap(rows, out):
    """Lemma fire counts per verification case (paper Fig. 7 heatmap)."""
    verify = _cases()
    sec = out.setdefault("fig7", {})
    for case in ["tp_layer", "ep_moe", "sp_moe", "ln_grad"]:
        report = verify(case)
        sec[case] = dict(sorted(report.stats["lemma_fires"].items()))
        for lemma, n in sorted(report.stats["lemma_fires"].items()):
            rows.append((f"fig7/{case}/{lemma}", 0.0, n))


def kernels_bench(rows, out):
    """Pallas kernel wall time (interpret mode on CPU — correctness path)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.rmsnorm import rmsnorm
    from repro.kernels import ref
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    s = jnp.asarray(rng.normal(size=(512,)), jnp.float32)
    sec = out.setdefault("kernels", {})
    t0 = time.perf_counter()
    rmsnorm(x, s, interpret=True).block_until_ready()
    dt = (time.perf_counter() - t0) * 1e6
    sec["rmsnorm_interp_us"] = round(dt, 1)
    rows.append(("kernels/rmsnorm_interp", dt, x.size))
    t0 = time.perf_counter()
    ref.rmsnorm_ref(x, s).block_until_ready()
    dt = (time.perf_counter() - t0) * 1e6
    sec["rmsnorm_ref_us"] = round(dt, 1)
    rows.append(("kernels/rmsnorm_ref", dt, x.size))


def _pin_hash_seed() -> None:
    """Re-exec with ``PYTHONHASHSEED=0`` unless already pinned.

    Saturation explores in set-iteration order, so lemma fire counts are
    only run-to-run reproducible under a fixed hash seed — and the
    ``lemma_fires`` determinism gate in scripts/check_bench.py compares
    them with exact equality.  Timings are unaffected either way."""
    if os.environ.get("PYTHONHASHSEED") == "0":
        return
    env = dict(os.environ, PYTHONHASHSEED="0")
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def main(argv=None) -> None:
    _pin_hash_seed()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="verification sections only, median-of-3 (stable "
                         "enough for the bench gate without the full run)")
    ap.add_argument("--repeats", type=int, default=REPEATS)
    ap.add_argument("--json", default=None,
                    help="output path (default: BENCH_verify.json, or "
                         "BENCH_verify_smoke.json under --smoke so smoke "
                         "runs never clobber the tracked full artifact)")
    args = ap.parse_args(argv)
    # a single repeat is too noisy to gate on (scripts/check_bench.py
    # compares these medians against BENCH_verify.json)
    repeats = min(3, args.repeats) if args.smoke else args.repeats
    if args.json is None:
        args.json = "BENCH_verify_smoke.json" if args.smoke \
            else "BENCH_verify.json"

    rows = []
    out = {"schema": 2, "repeats": repeats}
    sections = [
        lambda: fig4_verification_time(rows, out, repeats),
        lambda: fig5_scaling(rows, out, repeats),
        lambda: modelcheck_bench(rows, out, repeats),
        lambda: gradcheck_bench(rows, out, repeats),
        lambda: servecheck_bench(rows, out, repeats),
        lambda: runtime_bench(rows, out, repeats),
    ]
    names = ["fig4_verification_time", "fig5_scaling", "modelcheck_bench",
             "gradcheck_bench", "servecheck_bench", "runtime_bench"]
    if not args.smoke:
        sections += [
            lambda: fam_scaling(rows, out, repeats),
            lambda: suite_runner(rows, out, repeats),
            lambda: ablation_engine(rows, out, repeats),
            lambda: fig6_lemma_effort(rows, out),
            lambda: fig7_lemma_heatmap(rows, out),
            lambda: kernels_bench(rows, out),
        ]
        names += ["fam_scaling", "suite_runner", "ablation_engine",
                  "fig6_lemma_effort", "fig7_lemma_heatmap", "kernels_bench"]
    for name, section in zip(names, sections):
        try:
            section()
        except Exception as e:  # noqa: BLE001 — report per-section
            rows.append((f"{name}/ERROR({type(e).__name__})", 0.0, 0))
            out.setdefault("errors", {})[name] = f"{type(e).__name__}: {e}"
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    with open(args.json, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
    print(f"[bench] wrote {args.json}", file=sys.stderr)
    if "errors" in out:
        print(f"[bench] sections raised: {sorted(out['errors'])}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

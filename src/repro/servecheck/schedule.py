"""Scheduler: fan unique serving obligations across the shared runtime.

``check_serve`` is the subsystem entry point.  Unique obligations (after
position-class dedup) are verified in-process or on a supervised spawn
pool (:mod:`repro.runtime`) — workers receive only picklable
``(strategy, degree, bug, key)`` tuples and rebuild the obligation from
the deterministic registry, so nothing unpicklable crosses the boundary
and reports stay byte-identical for any worker count.  ``timeout_s``
budgets each obligation individually from the moment it starts on a
worker; ``cache=`` attaches the persistent certificate cache keyed by
:func:`repro.runtime.serve_cache_key` (strategy + obligation content
digest), so a warm re-run replays every serve verdict from disk.
"""
from __future__ import annotations

import time
from functools import partial
from typing import Dict, List, Optional, Tuple

from ..api.report import Report
from ..api.runner import _engine_opts, prove
from ..api.spec import Degree, task_id
from ..core import RefinementError
from ..core.explain import aggregate_explanations
from ..modelcheck.obligations import Obligation
from ..modelcheck.schedule import certified_stats, seam_check
from ..obs import trace as obs_trace
from ..runtime import (RuntimeTask, pool_stats, resolve_cache, run_tasks,
                       serve_cache_key)
from .obligations import ServeStrategy, get_serve_strategy
from .report import ServeReport, StepResult

DEFAULT_TIMEOUT_S = 600.0


def _expected_for(ob: Obligation, entry: ServeStrategy) -> str:
    bug = dict(ob.structure).get("bug", "-")
    return "certificate" if bug == "-" else entry.bug_spec(bug).expected


def _verify_obligation(ob: Obligation, name: str, expected: str,
                       engine_opts: Optional[dict] = None) -> dict:
    """Verify one serving obligation; returns a JSON-ready nested Report
    dict with the cache seam check (inferred R_o vs the relation the
    cache's PartitionSpec promises) attached — the seam is what catches
    the paper's silent-misplacement mode, where a wrong-axis collective
    still *refines* but assembles the cache off-spec."""
    bug = dict(ob.structure).get("bug", "-")
    bug = None if bug == "-" else bug
    degree = tuple(s for _, s in ob.mesh_axes)
    phase_s: dict = {}
    t0 = time.perf_counter()
    try:
        with _engine_opts(engine_opts) as eo:
            gs, gd, cert = prove(ob.seq_fn, ob.dist_fn, dict(ob.mesh_axes),
                                 ob.in_specs, ob.avals, ob.input_names, eo,
                                 name, phase_s)
    except RefinementError as e:
        return Report(
            case=name, degree=degree, bug=bug,
            verdict="refinement_error", expected=expected,
            ok=expected == "refinement_error", localization=e.payload(),
            stats={"phase_s": phase_s},
            explanation=getattr(e, "explanation", None),
            wall_s=round(time.perf_counter() - t0, 6)).to_json()
    except Exception as e:  # noqa: BLE001 — capture/engine failure -> verdict
        return Report(
            case=name, degree=degree, bug=bug,
            verdict="error", expected=expected, ok=False,
            error=f"{type(e).__name__}: {e}",
            wall_s=round(time.perf_counter() - t0, 6)).to_json()

    seams, seams_ok = seam_check(ob, gs, gd, cert)
    cert_json = cert.to_json()
    ok = seams_ok if expected == "certificate" else \
        (expected == "unexpected_relation" and not seams_ok)
    d = Report(
        case=name, degree=degree, bug=bug,
        verdict="certificate", expected=expected, ok=ok,
        r_o=cert_json["r_o"], stats=certified_stats(cert_json, phase_s),
        explanation=cert.explanation,
        wall_s=round(time.perf_counter() - t0, 6)).to_json()
    d["seams"] = seams
    return d


def _pool_task(strategy: str, degree: Degree, bug: Optional[str],
               key: str, engine_opts: Optional[dict]) -> dict:
    """Pool worker: rebuild the (deterministic) obligation set and verify
    the obligation addressed by ``key``."""
    entry = get_serve_strategy(strategy)
    ob = entry.build(degree=degree, bug=bug).unique[key]
    base = f"serve@{task_id(strategy, degree, bug)}"
    return _verify_obligation(ob, f"{base}:{key}",
                              _expected_for(ob, entry), engine_opts)


def _outcome_report(ob: Obligation, entry: ServeStrategy, name: str,
                    outcome) -> dict:
    """Convert a runtime outcome into this obligation's report dict."""
    if outcome.ok:
        d = dict(outcome.value)
        if outcome.cache == "hit":
            # cache entries are content-addressed — re-label for this run
            d["case"] = name
        info = outcome.runtime_info()
        if info:
            d["runtime"] = info
        return d
    verdict = "timeout" if outcome.status == "timeout" else "error"
    return Report(
        case=name, degree=tuple(s for _, s in ob.mesh_axes), bug=None,
        verdict=verdict, expected=_expected_for(ob, entry), ok=False,
        error=outcome.error, wall_s=round(outcome.wall_s, 6),
        runtime=outcome.runtime_info() or None).to_json()


def run_serve_obligations(strategy: str, degree: Degree,
                          bug: Optional[str] = None,
                          workers: Optional[int] = None,
                          engine_opts: Optional[dict] = None,
                          timeout_s: float = DEFAULT_TIMEOUT_S,
                          cache=None
                          ) -> Tuple[Dict[str, dict], int, Optional[dict],
                                     dict]:
    """Verify the strategy's unique serving obligations.

    Returns ``({obligation key: report dict}, workers actually used,
    cache stats or None, runtime pool stats)``.  ``timeout_s`` budgets
    each obligation individually; ``cache`` takes anything
    :func:`repro.runtime.resolve_cache` accepts.
    """
    entry = get_serve_strategy(strategy)
    obset = entry.build(degree=degree, bug=bug)
    keys = obset.keys_in_order()
    if workers is None:
        # dedup leaves a handful of obligations, most sub-second; fan out
        # only when there is genuinely parallel work
        workers = min(4, len(keys)) if len(keys) > 4 else 1
    cache = resolve_cache(cache)
    base = f"serve@{task_id(strategy, degree, bug)}"
    tasks = []
    for key in keys:
        ob = obset.unique[key]
        tasks.append(RuntimeTask(
            key=key, fn=_pool_task,
            args=(strategy, degree, bug, key, engine_opts),
            budget_s=timeout_s,
            cache_key=None if cache is None
            else serve_cache_key(strategy, key, engine_opts),
            local_fn=partial(_verify_obligation, ob, f"{base}:{key}",
                             _expected_for(ob, entry), engine_opts)))
    used = min(workers, len(keys)) or 1
    # spawn, not fork: the parent has traced jax by now (see modelcheck)
    outcomes = run_tasks(tasks, used, mp_method="spawn", cache=cache)
    reports = {key: _outcome_report(obset.unique[key], entry,
                                    f"{base}:{key}", outcomes[key])
               for key in keys}
    cache_stats = None if cache is None else {
        "dir": cache.dir,
        "hits": sum(1 for o in outcomes.values() if o.cache == "hit"),
        "misses": sum(1 for o in outcomes.values() if o.cache == "miss"),
        "entries": len(cache),
        "recovered_corrupt": cache.recovered_corrupt}
    return reports, used, cache_stats, pool_stats(outcomes)


def check_serve(strategy: str, *, degree: Optional[Degree] = None,
                bug: Optional[str] = None, workers: Optional[int] = None,
                engine_opts: Optional[dict] = None,
                timeout_s: float = DEFAULT_TIMEOUT_S,
                cache=None) -> ServeReport:
    """Serving-path refinement check: decode steps + prefill read, deduped
    by position class, verified, stitched.

    Returns a :class:`ServeReport`; never raises on verification failures
    (they become step verdicts) — only on caller mistakes (unknown
    strategy / bug / degree).  ``cache`` attaches the persistent
    certificate cache (see :func:`repro.runtime.resolve_cache`).
    """
    t0 = time.perf_counter()
    entry = get_serve_strategy(strategy)
    if degree is None:
        degree = entry.degrees[0]
    degree = entry.validate_degree(degree)
    if bug is not None and bug not in entry.bug_names():
        raise ValueError(
            f"bug `{bug}` is not hosted by serve strategy `{strategy}` "
            f"(hosted: {sorted(entry.bug_names()) or '-'})")
    with obs_trace.span("decompose", cat="capture"):
        obset = entry.build(degree=degree, bug=bug)
    obs_trace.event("dedup", cat="engine", subsystem="servecheck",
                    total=obset.total_blocks, unique=obset.n_unique)
    reports, used, cache_stats, pstats = run_serve_obligations(
        strategy, degree, bug=bug, workers=workers,
        engine_opts=engine_opts, timeout_s=timeout_s, cache=cache)
    with obs_trace.span("stitch", cat="engine"):
        steps: List[StepResult] = []
        failing: List[str] = []
        seen: set = set()
        for name, key in obset.blocks:
            rep = reports[key]
            ob = obset.unique[key]
            seams = rep.get("seams") or []
            relation_ok = all(s["ok"] for s in seams) if seams else \
                rep["verdict"] == "certificate"
            loc = rep.get("localization") or {}
            steps.append(StepResult(
                step=name, pos_class=dict(ob.structure)["pos_class"],
                obligation=key, verdict=rep["verdict"],
                relation_ok=relation_ok, cached=key in seen,
                localized_op=loc.get("op_name")))
            seen.add(key)
            if rep["verdict"] != "certificate" or not relation_ok:
                failing.append(name)

        verdicts = {s.verdict for s in steps}
        if verdicts & {"error", "timeout"}:
            verdict = "error"
        elif "refinement_error" in verdicts:
            verdict = "refinement_error"
        elif any(not s.relation_ok for s in steps):
            verdict = "unexpected_relation"
        else:
            verdict = "certificate"

        bug_step = entry.bug_steps.get(bug) if bug else None
        if bug is None:
            ok = verdict == "certificate"
        else:
            # the injected serving bug must surface the way its BugSpec
            # declares (refinement_error raise, or unexpected_relation via
            # the cache seam) AND localize to exactly its decode step — the
            # position-class siblings of the bugged step must stay clean
            ok = (verdict == entry.bug_spec(bug).expected
                  and failing == [f"step{bug_step}"])

        return ServeReport(
            strategy=strategy, degree=degree, verdict=verdict, ok=ok,
            steps=steps, reports=dict(reports),
            total_steps=obset.total_blocks,
            unique_obligations=obset.n_unique,
            dedup_ratio=round(obset.dedup_ratio, 3),
            failing_steps=failing, bug=bug, bug_step=bug_step,
            wall_s=round(time.perf_counter() - t0, 6), workers=used,
            cache=cache_stats, pool=pstats,
            explanation=aggregate_explanations(reports))

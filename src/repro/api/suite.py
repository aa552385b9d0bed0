"""``Suite`` — fan a (cases × degrees × bugs) matrix across a process pool.

    from repro.api import Suite
    result = Suite(degrees=(2,)).run(workers=4)      # clean matrix
    result = Suite(include_bugs=True).run()          # + all hosted bugs
    print(result.to_markdown()); result.write("suite.json")

Semantics:

* Tasks are the cross product of ``cases`` × ``degrees``, each case's
  hosted bugs riding along when ``include_bugs`` (bugs only run under the
  degrees their host case supports).
* ``run(workers=0)`` (or 1) executes in-process sequentially;
  ``workers >= 2`` fans out on the shared fault-tolerant runtime
  (:mod:`repro.runtime`): a supervised pool (spawn once this process's
  JAX backend is up, fork before that where the platform has it; workers
  are pinned to the CPU backend) whose warmed workers persist on the Suite
  instance across ``run`` calls — call ``shutdown()`` or use the Suite as
  a context manager to release them.  Workers receive only
  ``(case, degree, bug)`` name triples and rebuild specs from the
  registry, so nothing unpicklable crosses the boundary.
* Results are ordered by the task matrix — never by completion order —
  and the engine's deterministic tie-breaks make certificates (the
  ``r_o`` strings) byte-identical for any worker count and any
  ``GRAPHGUARD_OPT`` setting (covered by ``tests/test_api.py``).
* ``timeout_s`` is the *per-task* budget.  On pool runs the runtime
  enforces it from the moment the task starts on a worker (heartbeat
  tracked), reports the offender as ``verdict="timeout"`` with its
  measured elapsed time, kills the wedged worker with its pool, and
  resumes the rest on a replacement pool.  A crashed worker
  (``BrokenProcessPool``) quarantines the tasks it was running onto
  bounded retries with the exit cause recorded in the error string; a
  pool that cannot be rebuilt degrades to in-process execution with a
  structured ``degraded_reason`` in every affected Report.  In-process
  sequential runs cannot interrupt themselves, so budgets are not
  enforced there.
* ``cache=`` attaches the persistent certificate cache
  (:class:`repro.runtime.CertificateCache`): deterministic verdicts are
  committed as they complete, repeat tasks are served as cache hits with
  byte-identical certificates, and an interrupted run resumes from its
  last committed task.

CLI (also the CI golden gate — see scripts/ci.sh `suite`):

    python -m repro.api [--cases ...] [--degrees 2 4] [--bugs]
        [--workers N] [--timeout S] [--cache [DIR] | --no-cache]
        [--json PATH] [--markdown PATH]
        [--check GOLDEN | --write-golden GOLDEN]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

# Back-compat re-exports: these lived here before the fault-tolerant
# runtime was factored out into repro.runtime.
from ..runtime import (RuntimeTask, SupervisedPool,  # noqa: F401
                       execute_inline, pool_stats, resolve_cache,
                       strategy_cache_key, terminate_pool)
from ..runtime.pool import _warm_worker  # noqa: F401 — legacy import path
from .registry import build_spec, get_strategy, list_bugs, list_strategies
from .report import Report
from .runner import verify
from .spec import Degree, normalize_degree, parse_degree
from .spec import task_id as spec_task_id


@dataclass(frozen=True)
class SuiteTask:
    """One cell of the suite matrix: (case, degree, optional bug)."""
    case: str
    degree: Degree                       # int, or one entry per mesh axis
    bug: Optional[str] = None

    def task_id(self) -> str:
        return spec_task_id(self.case, self.degree, self.bug)


def _run_task(task: Tuple[str, int, Optional[str]],
              engine_opts: Optional[dict]) -> dict:
    """Pool worker: rebuild the spec by name and return a JSON-ready dict."""
    case, degree, bug = task
    return verify(case, degree=degree, bug=bug,
                  engine_opts=engine_opts).to_json()


class SuiteResult:
    """Ordered reports + aggregation to JSON / Markdown."""

    def __init__(self, reports: List[Report], wall_s: float, workers: int,
                 cache: Optional[dict] = None,
                 runtime: Optional[dict] = None):
        self.reports = reports
        self.wall_s = wall_s
        self.workers = workers
        self.cache = cache               # persistent-cache stats, if used
        self.runtime = runtime           # pool_stats() aggregate, if pooled

    @property
    def ok(self) -> bool:
        return all(r.ok for r in self.reports)

    def __iter__(self):
        return iter(self.reports)

    def __len__(self):
        return len(self.reports)

    def summary(self) -> dict:
        verdicts: Dict[str, int] = {}
        for r in self.reports:
            verdicts[r.verdict] = verdicts.get(r.verdict, 0) + 1
        out = {
            "total": len(self.reports),
            "ok": sum(r.ok for r in self.reports),
            "not_ok": [r.task_id() for r in self.reports if not r.ok],
            "verdicts": dict(sorted(verdicts.items())),
            "wall_s": round(self.wall_s, 3),
            "workers": self.workers,
        }
        if self.cache is not None:
            out["cache"] = self.cache
        if self.runtime is not None:
            # queue-wait vs on-worker wall aggregate (repro.runtime
            # pool_stats) — timing-class, so never in stable_summary()
            out["runtime"] = self.runtime
        return out

    def stable_summary(self) -> dict:
        """Timing-free view keyed by task id — the golden-diff artifact."""
        return {r.task_id(): r.stable_summary() for r in self.reports}

    def to_json(self) -> dict:
        return {
            "schema": 1,
            "summary": self.summary(),
            "reports": [r.to_json() for r in self.reports],
        }

    def to_markdown(self) -> str:
        lines = [
            "| task | verdict | expected | ok | wall ms |",
            "|------|---------|----------|----|--------:|",
        ]
        for r in self.reports:
            lines.append(
                f"| {r.task_id()} | {r.verdict} | {r.expected} "
                f"| {'yes' if r.ok else '**NO**'} | {r.wall_s * 1e3:.1f} |")
        s = self.summary()
        lines.append("")
        lines.append(f"{s['ok']}/{s['total']} tasks matched expectation in "
                     f"{s['wall_s']:.2f}s ({s['workers']} workers).")
        if self.cache is not None:
            lines.append(f"Certificate cache: {self.cache['hits']} hit(s), "
                         f"{self.cache['misses']} miss(es) "
                         f"({self.cache['dir']}).")
        return "\n".join(lines)

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)


class Suite:
    """A verification task matrix with a fault-tolerant parallel runner."""

    def __init__(self, cases: Optional[Sequence[str]] = None,
                 degrees: Optional[Sequence[int]] = None,
                 include_bugs: bool = False,
                 bugs: Optional[Sequence[str]] = None,
                 engine_opts: Optional[dict] = None):
        self.cases = tuple(cases) if cases is not None else list_strategies()
        for c in self.cases:
            get_strategy(c)              # fail fast on unknown names
        self.degrees = tuple(normalize_degree(d) for d in degrees) \
            if degrees is not None else None
        if self.degrees is not None:
            for c in self.cases:         # fail fast: a tuple degree on a
                for d in self.degrees:   # single-axis case would abort the
                    get_strategy(c).validate_degree(d)  # run mid-matrix
        self.include_bugs = include_bugs or bugs is not None
        self.bugs = tuple(bugs) if bugs is not None else None
        if self.bugs:
            hosted = list_bugs()
            for b in self.bugs:          # fail fast: a typo would otherwise
                if b not in hosted:      # silently yield zero bug tasks
                    raise KeyError(f"unknown bug `{b}` — registered: "
                                   f"{sorted(hosted)}")
                if hosted[b][0] not in self.cases:
                    raise ValueError(
                        f"bug `{b}` is hosted by case `{hosted[b][0]}`, "
                        f"which is not in this suite's cases — it would "
                        f"never run")
        self.engine_opts = engine_opts
        self._pool: Optional[SupervisedPool] = None
        self._pool_workers = 0

    def tasks(self) -> List[SuiteTask]:
        out: List[SuiteTask] = []
        for case in self.cases:
            entry = get_strategy(case)
            degrees = self.degrees if self.degrees is not None \
                else entry.degrees
            for deg in degrees:
                out.append(SuiteTask(case, deg))
                if not self.include_bugs:
                    continue
                for b in entry.bugs:
                    if self.bugs is not None and b.name not in self.bugs:
                        continue
                    out.append(SuiteTask(case, deg, b.name))
        return out

    # -- execution ----------------------------------------------------------
    def run(self, workers: Optional[int] = None,
            timeout_s: float = 120.0, cache=None,
            mp_method: Optional[str] = None) -> SuiteResult:
        """Run the matrix; ``cache`` takes anything
        :func:`repro.runtime.resolve_cache` accepts (a directory path, an
        open :class:`CertificateCache`, True for the default location,
        None to consult ``$GRAPHGUARD_CACHE_DIR``).  ``mp_method``
        overrides the worker start method (None = spawn once the JAX
        backend is up, else fork where available; "spawn" sidesteps
        fork-after-jax hazards in threaded hosts at the cost of per-worker
        interpreter start-up)."""
        tasks = self.tasks()
        if workers is None:
            workers = min(4, len(tasks)) or 1
        cache = resolve_cache(cache)
        t0 = time.perf_counter()
        rts = [self._runtime_task(t, timeout_s, cache) for t in tasks]
        if workers <= 1:
            outcomes = execute_inline(rts, cache=cache)
        else:
            outcomes = self._get_pool(min(workers, len(rts)) or 1,
                                      mp_method).execute(rts, cache=cache)
        reports = [Report.from_json(self._outcome_dict(t, outcomes[t.task_id()]))
                   for t in tasks]
        hits = sum(1 for o in outcomes.values() if o.cache == "hit")
        misses = sum(1 for o in outcomes.values() if o.cache == "miss")
        cache_stats = None if cache is None else \
            {"dir": cache.dir, "hits": hits, "misses": misses,
             "entries": len(cache),
             "recovered_corrupt": cache.recovered_corrupt}
        return SuiteResult(reports, time.perf_counter() - t0, workers,
                           cache=cache_stats, runtime=pool_stats(outcomes))

    def _runtime_task(self, task: SuiteTask, timeout_s: float,
                      cache) -> RuntimeTask:
        cache_key = None
        if cache is not None:
            # content-addressed: mesh + shapes + dtypes + input specs, so
            # an edited strategy re-proves while untouched ones hit
            cache_key = strategy_cache_key(
                build_spec(task.case, degree=task.degree, bug=task.bug),
                self.engine_opts)
        return RuntimeTask(
            key=task.task_id(), fn=_run_task,
            args=((task.case, task.degree, task.bug), self.engine_opts),
            budget_s=timeout_s, cache_key=cache_key)

    def _outcome_dict(self, task: SuiteTask, outcome) -> dict:
        """Convert a runtime outcome into a Report-shaped dict with the
        fault attributed to exactly this task."""
        if outcome.ok:
            d = dict(outcome.value)
            info = outcome.runtime_info()
            if info:
                d["runtime"] = info
            return d
        verdict = "timeout" if outcome.status == "timeout" else "error"
        return Report(
            case=task.case, degree=task.degree, bug=task.bug,
            verdict=verdict, expected=self._expected(task), ok=False,
            error=outcome.error, wall_s=round(outcome.wall_s, 6),
            runtime=outcome.runtime_info() or None).to_json()

    # -- pool lifecycle -----------------------------------------------------
    def _get_pool(self, workers: int,
                  mp_method: Optional[str] = None) -> SupervisedPool:
        """Create (or reuse) the supervised worker pool.

        The pool persists on the Suite instance across ``run`` calls: the
        per-worker jax backend re-initialization (see
        ``repro.runtime.pool._warm_worker``) is paid once, so repeated
        matrix sweeps run at steady-state speed.  Call :meth:`shutdown`
        (or use the Suite as a context manager) to release the processes.
        """
        if self._pool is not None and \
                (self._pool_workers != workers
                 or (mp_method is not None
                     and self._pool.mp_method != mp_method)):
            self.shutdown()
        if self._pool is None:
            self._pool = SupervisedPool(workers, mp_method=mp_method)
            self._pool_workers = workers
        return self._pool

    def shutdown(self) -> None:
        """Release the pool without blocking on wedged workers (see
        :func:`repro.runtime.terminate_pool`)."""
        if self._pool is not None:
            self._pool.shutdown()
            self._pool = None
            self._pool_workers = 0

    def __enter__(self) -> "Suite":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()

    @staticmethod
    def _expected(task: SuiteTask) -> str:
        entry = get_strategy(task.case)
        if task.bug is None:
            return entry.expected
        return entry.bug_spec(task.bug).expected


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

# The checked-in CI golden: the clean degree-2 matrix's stable summary.
# ``--check`` diffs against it (make suite / scripts/ci.sh suite);
# ``--update-golden`` / ``make golden`` regenerates it deterministically.
DEFAULT_GOLDEN = "tests/golden/suite_degree2.json"
GOLDEN_DEGREES = (2,)


def update_golden(path: str = DEFAULT_GOLDEN, workers: int = 4,
                  timeout_s: float = 120.0) -> int:
    """Deterministically regenerate the checked-in golden.

    Certificates are byte-identical for any worker count (covered by
    ``tests/test_api.py``), so the output depends only on the registered
    strategies.  A matrix that misses its own expectations is refused —
    a golden must never encode a failing suite.
    """
    with Suite(degrees=GOLDEN_DEGREES) as suite:
        result = suite.run(workers=workers, timeout_s=timeout_s)
    if not result.ok:
        print(f"[suite] REFUSING to write golden: tasks missed their "
              f"expectation: {result.summary()['not_ok']}", file=sys.stderr)
        return 1
    with open(path, "w") as f:
        json.dump(result.stable_summary(), f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"[suite] regenerated golden {path} "
          f"({len(result)} tasks)", file=sys.stderr)
    return 0


def add_cache_flags(ap: argparse.ArgumentParser) -> None:
    """The shared --cache/--no-cache pair (also used by launch/verify)."""
    from ..runtime import DEFAULT_CACHE_DIR
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--cache", nargs="?", const=True, default=None,
                   metavar="DIR",
                   help="persistent certificate cache: --cache DIR uses "
                        f"DIR, bare --cache uses {DEFAULT_CACHE_DIR}/ "
                        "(default: on only when $GRAPHGUARD_CACHE_DIR "
                        "is set)")
    g.add_argument("--no-cache", action="store_true",
                   help="disable the certificate cache even if "
                        "$GRAPHGUARD_CACHE_DIR is set")


def cache_from_args(args):
    """Map the flag pair onto :func:`repro.runtime.resolve_cache` input."""
    if args.no_cache:
        return False
    return args.cache                    # None -> env default; True/DIR


def main(argv=None) -> int:
    """CLI for ``python -m repro.api``: run the suite matrix in parallel."""
    ap = argparse.ArgumentParser(
        prog="python -m repro.api",
        description="Run the verification suite matrix in parallel.")
    ap.add_argument("--cases", nargs="*", default=None,
                    help="cases to run (default: every registered strategy)")
    ap.add_argument("--degrees", nargs="*", type=parse_degree, default=None,
                    help="parallelism degrees — ints like `2 4`, or "
                         "per-mesh-axis values like `4x2` for 2D cases "
                         "(default: per-case registry metadata)")
    ap.add_argument("--bugs", action="store_true",
                    help="also run every hosted bug variant")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--timeout", type=float, default=120.0,
                    help="per-task timeout in seconds")
    add_cache_flags(ap)
    ap.add_argument("--json", default=None, help="write full report JSON")
    ap.add_argument("--markdown", default=None, help="write Markdown table")
    ap.add_argument("--check", default=None, metavar="GOLDEN",
                    help="diff the stable summary against a golden JSON "
                         "and fail on mismatch")
    ap.add_argument("--write-golden", default=None, metavar="GOLDEN",
                    help="write the stable summary as the new golden")
    ap.add_argument("--update-golden", action="store_true",
                    help="regenerate the checked-in CI golden "
                         f"({DEFAULT_GOLDEN}) from the canonical clean "
                         "degree-2 matrix and exit (replaces hand-editing "
                         "when strategies change; refuses to bake in a "
                         "failing matrix)")
    args = ap.parse_args(argv)

    if args.update_golden:
        clash = [flag for flag, v in (
            ("--cases", args.cases), ("--degrees", args.degrees),
            ("--bugs", args.bugs or None), ("--json", args.json),
            ("--markdown", args.markdown), ("--check", args.check),
            ("--cache", args.cache),
            ("--write-golden", args.write_golden)) if v is not None]
        if clash:
            ap.error(f"--update-golden regenerates the canonical "
                     f"{DEFAULT_GOLDEN} matrix and cannot be combined with "
                     f"{', '.join(clash)} (use --write-golden PATH for a "
                     f"custom matrix)")
        return update_golden(workers=args.workers, timeout_s=args.timeout)

    suite = Suite(cases=args.cases, degrees=args.degrees,
                  include_bugs=args.bugs)
    result = suite.run(workers=args.workers, timeout_s=args.timeout,
                       cache=cache_from_args(args))
    print(result.to_markdown())
    if args.json:
        result.write(args.json)
        print(f"[suite] wrote {args.json}", file=sys.stderr)
    if args.markdown:
        with open(args.markdown, "w") as f:
            f.write(result.to_markdown() + "\n")
    if args.write_golden:
        with open(args.write_golden, "w") as f:
            json.dump(result.stable_summary(), f, indent=2, sort_keys=True)
        print(f"[suite] wrote golden {args.write_golden}", file=sys.stderr)
    rc = 0 if result.ok else 1
    if args.check:
        with open(args.check) as f:
            golden = json.load(f)
        got = result.stable_summary()
        if got != golden:
            missing = sorted(set(golden) - set(got))
            extra = sorted(set(got) - set(golden))
            changed = sorted(k for k in set(got) & set(golden)
                             if got[k] != golden[k])
            print(f"[suite] GOLDEN MISMATCH vs {args.check}: "
                  f"missing={missing} extra={extra} changed={changed}",
                  file=sys.stderr)
            for k in changed:
                print(f"  {k}:\n    golden: {golden[k]}\n    got:    {got[k]}",
                      file=sys.stderr)
            rc = 1
        else:
            print(f"[suite] matches golden {args.check}", file=sys.stderr)
    return rc

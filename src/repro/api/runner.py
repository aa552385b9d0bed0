"""``verify()`` — the library entry point for one verification task.

    from repro.api import verify
    report = verify("tp_layer", degree=4)            # -> Report
    report = verify("sp_rope", bug="rope_offset")    # verdict=refinement_error

Accepts a registered case name or an already-built ``StrategySpec``.
``engine_opts`` tunes the engine per call without touching process-global
state afterwards:

    max_nodes       e-graph node budget (default 400_000)
    optimizations   None (leave the process setting), bool (all flags), or
                    a {flag: bool} dict of ``repro.core.profile.OptConfig``
                    overrides — restored after the call either way
    explain         record proof provenance (lemma chains / failure
                    frontiers, see ``repro.core.explain``); None defers to
                    the ``GRAPHGUARD_EXPLAIN`` environment default

``run_spec()`` is the raising flavour (returns the live ``Certificate`` or
raises ``RefinementError``/``CaptureError``) used by the back-compat CLI
shim; ``verify()`` wraps it into a structured :class:`~repro.api.Report`.
"""
from __future__ import annotations

import time
from typing import Optional, Union

from ..core import (Certificate, RefinementError, capture, capture_spmd,
                    check_refinement, expand_spmd)
from ..core.profile import CONFIG, set_optimizations
from ..obs import trace as obs_trace
from .registry import build_spec
from .report import Report
from .spec import StrategySpec

DEFAULT_MAX_NODES = 400_000


def _resolve(spec_or_name: Union[str, StrategySpec], degree: Optional[int],
             bug: Optional[str]) -> StrategySpec:
    if isinstance(spec_or_name, StrategySpec):
        if degree is not None or bug is not None:
            raise ValueError(
                "degree=/bug= only apply when verifying by name; this "
                "StrategySpec is already built for "
                f"degree={spec_or_name.degree}, bug={spec_or_name.bug!r} — "
                "use dataclasses.replace / build_spec to change it")
        return spec_or_name
    return build_spec(spec_or_name, degree=2 if degree is None else degree,
                      bug=bug)


class _engine_opts:
    """Apply {max_nodes, optimizations} for the duration of one call."""

    def __init__(self, opts: Optional[dict]):
        opts = dict(opts or {})
        self.max_nodes = opts.pop("max_nodes", DEFAULT_MAX_NODES)
        self.optimizations = opts.pop("optimizations", None)
        self.explain = opts.pop("explain", None)
        if opts:
            raise ValueError(f"unknown engine_opts: {sorted(opts)}")
        if isinstance(self.optimizations, dict):
            unknown = set(self.optimizations) - set(CONFIG.as_dict())
            if unknown:
                raise ValueError(
                    f"unknown optimization flags: {sorted(unknown)} "
                    f"(valid: {sorted(CONFIG.as_dict())})")
        self._saved = None

    def __enter__(self):
        if self.optimizations is not None:
            self._saved = CONFIG.as_dict()
            if isinstance(self.optimizations, dict):
                set_optimizations(True, **{**self._saved,
                                           **self.optimizations})
            else:
                set_optimizations(bool(self.optimizations))
        return self

    def __exit__(self, *exc):
        if self._saved is not None:
            set_optimizations(True, **self._saved)
        return False


def prove(seq_fn, dist_fn, mesh_axes, in_specs, avals, input_names,
          eo: _engine_opts, case: str, phase_s: Optional[dict] = None):
    """Capture G_s and G_d, expand the SPMD capture into per-rank G_d and
    R_i, and run relation inference (raising).  Returns ``(gs, gd,
    certificate)``.

    Each phase is a span (``capture`` with ``graph`` gs or gd, ``expand``,
    ``infer``).  Capture and expansion are timed always, as the engine
    times its phases; their seconds go to ``phase_s["capture"]`` and
    ``phase_s["expand"]`` when ``phase_s`` is given, before inference
    can raise."""
    t0 = time.perf_counter()
    with obs_trace.span("capture", cat="capture", graph="gs", case=case):
        gs = capture(seq_fn, list(avals), list(input_names))
    with obs_trace.span("capture", cat="capture", graph="gd", case=case):
        cap = capture_spmd(dist_fn, mesh_axes, list(in_specs), list(avals),
                           list(input_names))
    t1 = time.perf_counter()
    with obs_trace.span("expand", cat="capture", case=case):
        gd, r_i = expand_spmd(cap)
    if phase_s is not None:
        phase_s["capture"] = t1 - t0
        phase_s["expand"] = time.perf_counter() - t1
    with obs_trace.span("infer", cat="engine", case=case):
        return gs, gd, check_refinement(gs, gd, r_i,
                                        max_nodes=eo.max_nodes,
                                        explain=eo.explain)


def run_spec(spec: StrategySpec, *, engine_opts: Optional[dict] = None
             ) -> Certificate:
    """Capture G_s/G_d, derive R_i, and run relation inference (raising)."""
    if not isinstance(engine_opts, _engine_opts):
        engine_opts = _engine_opts(engine_opts)
    with engine_opts as eo:
        return prove(spec.seq_fn, spec.dist_fn, spec.mesh_axes,
                     spec.in_specs, spec.avals, spec.input_names, eo,
                     spec.name)[2]


def verify(spec_or_name: Union[str, StrategySpec], *,
           degree: Optional[int] = None, bug: Optional[str] = None,
           engine_opts: Optional[dict] = None) -> Report:
    """Verify one task and return a structured :class:`Report`.

    ``degree`` (default 2) and ``bug`` select the task when verifying by
    name; passing them alongside an already-built ``StrategySpec`` raises
    rather than silently ignoring them.  Unknown case/bug names and the
    bug-under-wrong-case guard also raise (``KeyError``/``ValueError``):
    those are caller mistakes, not verification outcomes.  Engine-side
    failures become verdicts.
    """
    spec = _resolve(spec_or_name, degree, bug)
    engine_opts = _engine_opts(engine_opts)   # caller mistakes raise here
    t0 = time.perf_counter()
    try:
        cert = run_spec(spec, engine_opts=engine_opts)
    except RefinementError as e:
        verdict, payload = "refinement_error", e.payload()
        return Report(
            case=spec.name, degree=spec.degree, bug=spec.bug,
            verdict=verdict, expected=spec.expected,
            ok=spec.expected_verdict == verdict, localization=payload,
            explanation=getattr(e, "explanation", None),
            wall_s=round(time.perf_counter() - t0, 6))
    except Exception as e:  # noqa: BLE001 — CaptureError/engine -> verdict
        return Report(
            case=spec.name, degree=spec.degree, bug=spec.bug,
            verdict="error", expected=spec.expected, ok=False,
            error=f"{type(e).__name__}: {e}",
            wall_s=round(time.perf_counter() - t0, 6))
    cert_json = cert.to_json()
    return Report(
        case=spec.name, degree=spec.degree, bug=spec.bug,
        verdict="certificate", expected=spec.expected,
        ok=spec.expected_verdict == "certificate",
        r_o=cert_json["r_o"], stats=cert_json["stats"], certificate=cert,
        explanation=cert.explanation,
        wall_s=round(time.perf_counter() - t0, 6))

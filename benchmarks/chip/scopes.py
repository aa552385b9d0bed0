"""Device time per program scope, from a profiler trace and the step's HLO.

The program names its device work with ``jax.named_scope``: ``embed``,
``layers`` (the layer scan and loops), ``attn`` with ``kv_cache`` and
``attend`` inside it, ``mlp``, ``head`` and ``optimizer``.  A name reaches
the compiled HLO as the ``op_name`` metadata of each instruction, as in
``jit(step)/transpose(jvp(layers))/while/body/closed_call/attn/attend/
dot_general``; the device events of a trace carry only the instruction's
name.  So the reduction joins the two:

- :func:`hlo_scopes` reads the compiled step's HLO text
  (``compiled.as_text()``) into ``{instruction: (opcode, scope)}``.  An
  instruction's scope is the innermost scope name on its ``op_name`` path;
  transform wrappers such as ``jvp(...)`` and ``transpose(...)`` are
  peeled and remat frames passed over, so backward and recomputed
  operations land in their forward scope.  An instruction whose path names
  no scope takes the scope of the instruction that calls its computation:
  XLA's copies in the layer scan's body land in ``layers``.  One the
  compiler added outside any called computation (no ``op_name``) takes the
  scope of the value it reads, its first operand: the copies of the
  stacked KV cache out of the layer scan land in ``layers`` too.
- :func:`reduce` sums the device time of each scope inside the window,
  keyed by program and instruction name.  Only leaf operations count:
  ``while``, ``conditional`` and ``call`` hold the operations of their
  bodies, which the trace lists themselves.  What has no scope, in the
  step's program or another, is ``(unscoped)``.

Like ``devtrace.py`` it imports no JAX until it reads a file.
"""
from __future__ import annotations

import re
import sys
from collections import defaultdict
from pathlib import Path
from typing import Dict, Optional, Tuple

import harness
from devtrace import op_name

SCOPES = ("embed", "layers", "attn", "kv_cache", "attend", "mlp", "head",
          "optimizer")
UNSCOPED = "(unscoped)"
CONTAINERS = frozenset({"while", "conditional", "call"})

_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|\s)([a-z][\w\-]*)\(")
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)="
                         r"\{([^}]*)\}")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of :data:`SCOPES` on an ``op_name`` path, or None."""
    found = None
    for part in op_name.split("/"):
        while True:
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        if part in SCOPES:
            found = part
    return found


def hlo_scopes(text: str) -> Tuple[str, Dict[str, Tuple[str, Optional[str]]]]:
    """``(module name, {instruction: (opcode, scope or None)})`` of one
    module's HLO text."""
    module = ""
    comp = None
    # name: opcode, scope, computation, has op_name, first operand
    own: Dict[str, tuple] = {}
    callers: Dict[str, str] = {}          # computation: first caller
    for line in text.splitlines():
        head = _MODULE.match(line)
        if head:
            module = head.group(1)
            continue
        stripped = line.strip()
        if stripped.endswith("{") and " = " not in stripped:
            comp = stripped.split()[1 if stripped.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        name, rest = m.groups()
        op = _OPCODE.search(rest)
        path = _OP_NAME.search(rest)
        first = _OPERAND.search(rest, op.end()) if op else None
        own[name] = (op.group(1) if op else "",
                     scope_of(path.group(1)) if path else None, comp,
                     path is not None, first.group(1) if first else None)
        called = _CALLS.findall(rest)
        for group in _CALL_LISTS.findall(rest):
            called += [c.strip().lstrip("%") for c in group.split(",")]
        for c in called:
            callers.setdefault(c, name)

    memo: Dict[str, Optional[str]] = {}

    def scope(name: str) -> Optional[str]:
        if name not in memo:
            memo[name] = None                 # no cycle reads itself
            _, s, comp, has_path, first = own[name]
            if s is None and comp in callers:
                s = scope(callers[comp])
            elif s is None and not has_path and first in own:
                s = scope(first)
            memo[name] = s
        return memo[name]

    return module, {n: (own[n][0], scope(n)) for n in own}


def _module_of(name: str) -> str:
    """``jit_greedy(123)`` -> ``jit_greedy``: a program event's module."""
    return name.split("(", 1)[0]


def reduce(tr: dict, hlo: Dict[str, Dict[str, Tuple[str, Optional[str]]]],
           window: Optional[Tuple[float, float]] = None) -> dict:
    """Seconds of device time per scope inside the window, averaged over
    the device planes, with every scope of :data:`SCOPES` listed.

    ``tr`` is :func:`devtrace.extract`'s output, whose program events are
    named ``module:<name>``; ``hlo`` maps a module name to
    :func:`hlo_scopes`' table.  An operation belongs to the program whose
    event holds its start.  ``window`` is ``(start_ns, end_ns)``, by
    default the host span named ``window``.
    """
    if window is None:
        spans = [h for h in tr["host"] if h[0] == "window"]
        if not spans:
            raise ValueError("the trace has no `window` span")
        window = (spans[0][1], spans[0][2])
    lo, hi = window
    devices = tr["devices"]
    if not devices:
        raise ValueError("the trace has no device plane")
    out: Dict[str, float] = defaultdict(float)
    for evs in devices.values():
        programs = sorted((s, e, _module_of(n[len("module:"):]))
                          for n, s, e in evs if n.startswith("module:"))
        k = 0
        for name, s, e in sorted(((n, s, e) for n, s, e in evs
                                  if not n.startswith("module:")),
                                 key=lambda x: x[1]):
            while k < len(programs) and programs[k][1] <= s:
                k += 1
            module = programs[k][2] if (k < len(programs)
                                        and programs[k][0] <= s) else None
            op, scope = hlo.get(module, {}).get(op_name(name), ("", None))
            if op in CONTAINERS:
                continue
            t = min(e, hi) - max(s, lo)
            if t > 0:
                out[scope or UNSCOPED] += t * 1e-9 / len(devices)
    return {k: out.get(k, 0.0) for k in SCOPES + (UNSCOPED,)}


# ---------------------------------------------------------------------------
# the per-layer readings and a profiled run of a cell's step
# ---------------------------------------------------------------------------

# reading: the scopes it sums (``layers`` alone is the scan's own slicing
# and copies: the blocks' operations are in their innermost scope)
READINGS = {"attn_ms": ("attn", "kv_cache", "attend"), "mlp_ms": ("mlp",),
            "scan_ms": ("layers",), "head_ms": ("embed", "head"),
            "optimizer_ms": ("optimizer",)}


def per_step_ms(scopes: dict, steps: int) -> dict:
    """Milliseconds of device time per step of each reading."""
    return {k: 1e3 * sum(scopes[s] for s in v) / steps
            for k, v in READINGS.items()}


def _decode(cell, devices, seed):
    """The cell's compiled greedy step and a runner of ``n`` steps."""
    import numpy as np
    import jax
    step, params, cache, first, _ = harness.driver(cell).build(
        cell, devices, seed)
    state = {"tok": first, "cache": cache, "pos": cell.traffic["prompt"]}

    def run(n):
        for _ in range(n):
            with jax.profiler.TraceAnnotation("dispatch"):
                state["tok"], state["cache"] = step(
                    params, state["cache"], state["tok"],
                    np.int32(state["pos"]))
            with jax.profiler.TraceAnnotation("readback"):
                np.asarray(state["tok"])
            state["pos"] += 1
    return step.as_text(), run


def _train(cell, devices, seed):
    """The cell's compiled train step and a runner of ``n`` steps."""
    import jax
    step, params, opt, batches, _ = harness.driver(cell).build(
        cell, devices, seed)
    state = {"p": params, "o": opt, "k": 0}
    text = step.lower(params, opt, batches[0]).compile().as_text()

    def run(n):
        m = None
        for _ in range(n):
            with jax.profiler.TraceAnnotation("dispatch"):
                state["p"], state["o"], m = step(
                    state["p"], state["o"],
                    batches[state["k"] % len(batches)])
            state["k"] += 1
        with jax.profiler.TraceAnnotation("readback"):
            jax.block_until_ready((state["p"], state["o"], m))
    return text, run


def main(argv=None) -> int:
    """Profile ``--steps`` steps of a device cell and print, as the last
    line, the step time with the profiler off and on, the trace's busy
    time, the seconds of each scope and the readings per step."""
    import argparse
    import json
    import tempfile
    import time
    ap = argparse.ArgumentParser(description=main.__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=30)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(harness.ROOT / "src"))
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"[scopes] {e}", file=sys.stderr)
        return 2
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # the cache key leaves metadata out by default: a step compiled
    # before the scopes existed would be served without them
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    text, run = {"decode": _decode, "train": _train}[
        cell.traffic["driver"]](cell, devices, args.seed)
    run(3)                                    # warm
    t = time.perf_counter()
    run(args.steps)
    untraced = (time.perf_counter() - t) / args.steps
    tdir = tempfile.mkdtemp(prefix="chip-trace-")
    jax.profiler.start_trace(tdir)
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("window"):
        run(args.steps)
    traced = (time.perf_counter() - t) / args.steps
    jax.profiler.stop_trace()
    import shutil
    from devtrace import extract, reduce as busy
    tr = extract(tdir, ("window", "dispatch", "readback"))
    shutil.rmtree(tdir, ignore_errors=True)
    module, table = hlo_scopes(text)
    scopes = reduce(tr, {module: table})
    b = busy(tr)
    print(json.dumps({
        "workload": cell.name, "seed": args.seed, "steps": args.steps,
        "device": harness.describe(devices),
        "step_ms": {"untraced": 1e3 * untraced, "traced": 1e3 * traced},
        "busy_s": b["busy_s"], "window_s": b["window_s"], "scopes": scopes,
        "per_step_ms": per_step_ms(scopes, args.steps)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

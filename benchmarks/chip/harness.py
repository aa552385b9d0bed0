"""What every cell shares: finding a cell's files by name, the chip check,
the peaks table, the metric readers and the result line.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  Each lives in a file of its own:

- ``configs/<config>.json``: the sizes as run, the source and the cuts;
- ``traffic/<traffic>.json``: the parameters of the mix, including the
  ``driver`` (a module under ``drivers/``) that generates and times it;
- ``metrics/<metric>.py``: one reader per metric, ``read(run)`` returning
  a number or ``None`` when the run has nothing to read;
- ``families/<family>.py``: the configuration's family: its weights, their
  layout in the program and its plain reference;
- ``counts/<family>.py``: operations and bytes from shapes;
- ``limits/<cell>.json``: the limit of every number that decides the
  cell's ``correct``.

So a new cell, configuration, mix or metric is new files and new entries
in ``BENCHMARK.json``; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent          # benchmarks/chip
ROOT = HERE.parents[1]                          # the checkout


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: Dict[str, float]


@dataclass
class Check:
    """One number compared with its limit (``value <= limit`` passes)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Run:
    """What a driver hands back: the window and everything it recorded.

    ``records`` is the driver's own (per verdict, per step, ...);
    ``trace`` is the reduced profiler trace of a ``--trace 1`` run.
    """
    cell: Cell
    setup_s: float
    window_s: float
    attempted: int
    failed: int
    records: Dict[str, Any] = field(default_factory=dict)
    checks: List[Check] = field(default_factory=list)
    trace: Optional[dict] = None
    breakdown: Optional[dict] = None
    peak_bytes: Optional[int] = None
    device_kind: Optional[str] = None


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a harness file by path (the harness is not a package)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: Path = ROOT) -> dict:
    return _load_json(root / "BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench: Optional[dict] = None,
              base: Path = HERE) -> Cell:
    """The cell ``name`` with its configuration, mix, metrics and limits."""
    bench = bench if bench is not None else benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload `{name}` (known: {sorted(cells)})")
    w = cells[name]
    config = _load_json(base / "configs" / f"{w['config']}.json")
    if "family" in config and not _family_file(config["family"],
                                               base).exists():
        raise FileNotFoundError(
            f"configuration `{w['config']}` is of the family "
            f"`{config['family']}`, which has no file "
            f"{_family_file(config['family'], base)}")
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        traffic=_load_json(base / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        limits=_load_json(base / "limits" / f"{name}.json"))


def driver(cell: Cell, base: Path = HERE):
    """The general generator that the cell's mix names."""
    d = cell.traffic["driver"]
    return load_module(base / "drivers" / f"{d}.py", f"chip_driver_{d}")


def reader(metric: str, base: Path = HERE) -> Callable:
    """``metrics/<metric>.py``'s ``read``."""
    mod = load_module(base / "metrics" / f"{metric}.py",
                      "chip_metric_" + metric.replace(".", "_")
                      .replace("-", "_"))
    return mod.read


def _family_file(family: str, base: Path) -> Path:
    return base / "families" / f"{family}.py"


def family(name: str, base: Path = HERE):
    """``families/<name>.py``, which gives a driver by these names:
    ``SIZE_KEYS``, the configuration file's keys that set the program's
    sizes (:func:`program_config`); ``shapes(c)`` (leaf name -> shape);
    ``weights(c, seed, dtype, where)``, every leaf drawn from the seed in
    one jitted call onto ``where`` (a device, or a sharding per leaf);
    ``to_program`` and ``from_program``, the benchmark's layout to the
    program's parameter tree and back; ``served_gaps`` and
    ``control_gaps``, the reference's gaps of served tokens and of the
    fp8 control's choices, layer by layer; ``train``, the reference's
    AdamW steps."""
    path = _family_file(name, base)
    if not path.exists():
        raise FileNotFoundError(f"the family `{name}` has no file {path}")
    return load_module(path, "chip_family_" + name.replace("-", "_"))


def counts(family: str, base: Path = HERE):
    return load_module(base / "counts" / f"{family}.py",
                       f"chip_counts_{family}")


def peaks(device_kind: str, base: Path = HERE) -> dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = _load_json(base / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind `{device_kind}` in "
                       f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# set-up phases
# ---------------------------------------------------------------------------

_marks: List[tuple] = []


def mark(phase: str) -> None:
    """End the set-up phase ``phase`` now."""
    _marks.append((phase, time.perf_counter()))


def setup_phases(t0: float) -> Dict[str, float]:
    """Seconds of each set-up phase marked since ``t0``, in order."""
    out, last = {}, t0
    for phase, t in _marks:
        out[phase] = out.get(phase, 0.0) + t - last
        last = t
    return out


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------

def require_chips(n: int) -> list:
    """The first ``n`` TPU devices; raises :class:`NoChip` otherwise."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no accelerator: {e}") from e
    if not devices or devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform "
                     f"`{devices[0].platform if devices else None}`)")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def describe(devices) -> dict:
    import jax
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": jax.device_count()}


def peak_bytes(devices) -> Optional[int]:
    """Largest ``peak_bytes_in_use`` over ``devices`` (None if unknown)."""
    got = [(d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in devices]
    got = [p for p in got if p is not None]
    return max(got) if got else None


def rel_err(got, want) -> float:
    """max |got - want| over max |want|, in float64."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-30))


def seed31(seed: int) -> int:
    """A 31-bit key for ``jax.random`` from any whole-number seed."""
    import numpy as np
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0] >> 1)


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------

def metrics_of(run: Run, trace: bool, base: Path = HERE) -> Dict[str, dict]:
    """End-to-end metrics (``--trace 0``) or per-layer ones (``--trace 1``)
    read by each metric's own reader; a reader that finds nothing to read
    leaves its metric out."""
    out = {}
    for m in (run.cell.per_layer if trace else run.cell.end_to_end):
        value = reader(m["name"], base)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run: Run, device: dict, trace: bool,
                base: Path = HERE, phases: Optional[dict] = None) -> dict:
    correct = bool(run.checks) and all(c.ok for c in run.checks)
    line = {
        "correct": correct,
        "attempted": int(run.attempted),
        "failed": int(run.failed),
        "metrics": metrics_of(run, trace, base),
        "device": dict(device, memory_peak_bytes=run.peak_bytes),
    }
    if trace and run.trace is not None:
        line["device"]["busy_s"] = run.trace["busy_s"]
        line["device"]["window_s"] = run.trace["window_s"]
    if trace and run.breakdown is not None:
        line["breakdown"] = run.breakdown
    if phases is not None:
        line["setup_phases"] = phases
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in run.checks}
    return line


def print_checks(run: Run, stream=None) -> None:
    stream = stream or sys.stderr
    for c in run.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=stream)
    stream.flush()


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def program_config(c: dict, base: Path = HERE):
    """The program's registered configuration with the sizes of the
    configuration file: every key of the family's ``SIZE_KEYS``, each of
    which agrees with the program unless the file lists it as reduced."""
    from dataclasses import replace
    from repro.models.registry import load_config
    prog = load_config(c["program_config"])
    if prog.family != c["family"]:
        raise ValueError(f"{c['name']}: the family is `{c['family']}` in "
                         f"the configuration file and `{prog.family}` in "
                         f"the program")
    keys = family(c["family"], base).SIZE_KEYS
    missing = [k for k in keys if k not in c]
    if missing:
        raise KeyError(f"{c['name']}: the configuration file has no "
                       f"{missing}, which the family `{c['family']}` sizes")
    cut = set(c.get("reduced", ()))
    sizes = {k: tuple(c[k]) if isinstance(c[k], list) else c[k]
             for k in keys}
    for k in keys:
        if k not in cut and getattr(prog, k) != sizes[k]:
            raise ValueError(f"{c['name']}: `{k}` is {c[k]!r} in the "
                             f"configuration file and {getattr(prog, k)!r} "
                             f"in the program, and not listed as reduced")
    return replace(prog, **sizes)


def mesh_of(devices, shape):
    """A ``(data, model)`` mesh of ``shape`` over ``devices``."""
    import numpy as np
    from jax.sharding import AxisType, Mesh
    n = shape[0] * shape[1]
    return Mesh(np.array(devices[:n]).reshape(tuple(shape)),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)

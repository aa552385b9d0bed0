"""From a profiler trace to the device's busy and idle time.

:func:`extract` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists: the operations and programs of each device (the ``XLA Ops``
and ``XLA Modules`` lines of every ``/device:TPU:n`` plane) and the host's named spans (the harness's
``TraceAnnotation``\\ s).  :func:`reduce` works on those lists alone, so a
small recorded trace tests it without a chip and without libtpu.

- busy: the union of the device's operation and program intervals inside
  the window, averaged over the devices;
- idle gaps: the window minus the busy union, each piece attributed to
  the innermost host span around it (``none`` where the host was in no
  span), summed per span name;
- device ops: total device time per operation name;
- collectives: the union of the intervals of the collective operations
  (all-reduce, all-gather, reduce-scatter, all-to-all and
  collective-permute, and the ``-start`` and ``-done`` halves of each)
  inside the window, averaged over the devices.

Only the planes of the cell's own devices count: ``/device:TPU:0`` up to
its number of chips.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

DEVICE_LINES = ("XLA Ops", "XLA Modules")
TOP = 10
_COLLECTIVE = r"(?:all-reduce|all-gather|reduce-scatter|all-to-all|" \
              r"collective-permute)(?:-start|-done)?"
# the opcode of an HLO label, ``... bf16[8]{0} all-reduce-start(...)``, or
# the instruction's own name, ``all-gather.3``
_COLLECTIVE_OPCODE = re.compile(r"(?:^|\s)" + _COLLECTIVE + r"\(")
_COLLECTIVE_NAME = re.compile(_COLLECTIVE + r"(?:[.\-]|$)")
_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def op_name(label: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return label.split(" = ", 1)[0].lstrip("%").strip()


def is_collective(label: str) -> bool:
    """Whether a device event is a collective operation or one half of
    an asynchronous one."""
    if label.startswith("module:"):
        return False
    _, _, rest = label.partition(" = ")
    return bool(_COLLECTIVE_NAME.match(op_name(label))
                or _COLLECTIVE_OPCODE.search(rest))


def _chip(plane: str) -> float:
    """The device number of a ``/device:TPU:<n>`` plane."""
    m = _PLANE.match(plane)
    return int(m.group(1)) if m else float("inf")


def extract(trace_dir: str, host_spans: Iterable[str]) -> dict:
    """``{"devices": {plane: [Interval]}, "host": [Interval]}`` from the
    newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    wanted = set(host_spans)
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name in DEVICE_LINES:
                    tag = "module:" if line.name == "XLA Modules" else ""
                    ops.extend((tag + e.name, float(e.start_ns),
                                float(e.end_ns)) for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.end_ns))
                            for e in line.events if e.name in wanted)
    return {"devices": devices, "host": host}


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merge overlapping ``(start, end)`` pairs, sorted."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if min(e, hi) > max(s, lo)]


def _gaps(busy, lo, hi):
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def _attribute(gaps, host: List[Interval]) -> Dict[str, float]:
    """Seconds of each gap under the innermost (shortest) host span."""
    out: Dict[str, float] = defaultdict(float)
    host = sorted(host, key=lambda h: h[1])
    for g0, g1 in gaps:
        # cut the gap at every host span edge inside it
        edges = sorted({g0, g1} | {t for _, s, e in host for t in (s, e)
                                   if g0 < t < g1})
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            cover = [h for h in host if h[1] <= mid < h[2]]
            name = min(cover, key=lambda h: h[2] - h[1])[0] if cover \
                else "none"
            out[name] += (b - a) * 1e-9
    return out


def reduce(tr: dict, window: Optional[Tuple[float, float]] = None,
           chips: Optional[int] = None) -> dict:
    """Busy, idle and collective seconds, top device ops, collective ops
    and host-labelled idle time.

    ``window`` is ``(start_ns, end_ns)``; by default the host span named
    ``window``.  ``chips``: the cell's number of chips, whose planes alone
    count (all of them by default).  Busy and collective time are
    averaged over those planes.
    """
    if window is None:
        spans = [h for h in tr["host"] if h[0] == "window"]
        if not spans:
            raise ValueError("the trace has no `window` span")
        window = (spans[0][1], spans[0][2])
    lo, hi = window
    window_s = (hi - lo) * 1e-9
    devices = {p: evs for p, evs in tr["devices"].items()
               if chips is None or _chip(p) < chips}
    if not devices:
        raise ValueError("the trace has no device plane of the cell")
    n = len(devices)
    busy_each, coll_each, idle = [], [], defaultdict(float)
    ops: Dict[str, float] = defaultdict(float)
    coll_ops: Dict[str, float] = defaultdict(float)
    host = [h for h in tr["host"] if h[0] != "window"]
    for plane, evs in sorted(devices.items()):
        busy = union(_clip([(s, e) for _, s, e in evs], lo, hi))
        busy_each.append(sum(e - s for s, e in busy) * 1e-9)
        coll = [(name, s, e) for name, s, e in evs if is_collective(name)]
        coll_each.append(sum(e - s for s, e in union(
            _clip([(s, e) for _, s, e in coll], lo, hi))) * 1e-9)
        for name, s, e in evs:
            c = _clip([(s, e)], lo, hi)
            if c:
                ops[op_name(name)] += (c[0][1] - c[0][0]) * 1e-9 / n
        for name, s, e in coll:
            c = _clip([(s, e)], lo, hi)
            if c:
                coll_ops[op_name(name)] += (c[0][1] - c[0][0]) * 1e-9 / n
        for k, v in _attribute(_gaps(busy, lo, hi), host).items():
            idle[k] += v / n
    # programs (``module:``) cover their operations: list them only where
    # the trace has no operation inside them
    if any(not k.startswith("module:") for k in ops):
        ops = {k: v for k, v in ops.items() if not k.startswith("module:")}
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": sum(busy_each) / n, "window_s": window_s,
            "collective_s": sum(coll_each) / n,
            "device_ops": top(ops), "idle_gaps": top(idle),
            "collective_ops": top(coll_ops)}

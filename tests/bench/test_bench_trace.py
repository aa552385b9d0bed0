"""The reduction from a profiler trace to busy and idle time, on traces
that need no chip: a synthetic one with known answers and a small one
recorded on a v5e."""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from conftest import CHIP

import devtrace  # noqa: E402

MS = 1_000_000.0          # nanoseconds


def test_importing_the_harness_loads_no_backend():
    code = ("import sys; sys.path.insert(0, %r); import devtrace, harness; "
            "assert 'jax' not in sys.modules" % str(CHIP))
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


def test_union_merges_overlaps():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3],
                                                                 [5, 8]]


def test_synthetic_trace():
    tr = {"devices": {"/device:TPU:0": [
              ("fusion.1", 1 * MS, 4 * MS), ("fusion.2", 3 * MS, 5 * MS),
              ("copy", 7 * MS, 8 * MS), ("outside", 20 * MS, 30 * MS)]},
          "host": [("window", 0.0, 10 * MS),
                   ("dispatch", 0.0, 1 * MS), ("readback", 5 * MS, 9 * MS),
                   ("batch", 6 * MS, 6.5 * MS)]}
    r = devtrace.reduce(tr)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.005)          # [1,5] and [7,8]
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.5)
    assert dict((k, v) for k, v in r["device_ops"]) == pytest.approx(
        {"fusion.1": 0.003, "fusion.2": 0.002, "copy": 0.001})
    # idle [0,1] dispatch; [5,7] readback but [6,6.5] batch; [8,10] is
    # readback to 9 and none after
    assert dict((k, v) for k, v in r["idle_gaps"]) == pytest.approx(
        {"dispatch": 0.001, "readback": 0.0025, "batch": 0.0005,
         "none": 0.001})


def test_two_devices_are_averaged():
    tr = {"devices": {"/device:TPU:0": [("a", 0.0, 4 * MS)],
                      "/device:TPU:1": [("a", 0.0, 2 * MS)]},
          "host": [("window", 0.0, 4 * MS)]}
    r = devtrace.reduce(tr)
    assert r["busy_s"] == pytest.approx(0.003)


def test_recorded_v5e_trace():
    """A trace of five dispatch/readback steps recorded on a TPU v5 lite
    (``extract`` output, saved as JSON)."""
    path = Path(__file__).parent / "data" / "v5e_trace.json"
    tr = json.loads(path.read_text())
    r = devtrace.reduce(tr)
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]
    assert abs(sum(v for _, v in r["idle_gaps"])
               - (r["window_s"] - r["busy_s"])) < 1e-6
    names = {k for k, _ in r["idle_gaps"]}
    assert names <= {"dispatch", "readback", "none"}

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


def test_a_plane_outside_the_cell_is_ignored():
    tr = {"devices": {"/device:TPU:0": [("a", 0.0, 4 * MS)],
                      "/device:TPU:1": [("a", 0.0, 2 * MS)],
                      "/device:TPU:2": [("a", 0.0, 1 * MS)]},
          "host": [("window", 0.0, 4 * MS)]}
    assert devtrace.reduce(tr, chips=1)["busy_s"] == pytest.approx(0.004)
    assert devtrace.reduce(tr, chips=2)["busy_s"] == pytest.approx(0.003)
    assert devtrace.reduce(tr)["busy_s"] == pytest.approx(0.007 / 3)


def test_collective_time_on_a_synthetic_trace():
    """Collectives by opcode or by name, their asynchronous halves
    included, overlaps counted once, averaged over the cell's planes."""
    ar = ("%all-reduce-start.3 = (bf16[32,4096]{1,0}, bf16[32,4096]{1,0}) "
          "all-reduce-start(bf16[32,4096]{1,0} %fusion.2), channel_id=1")
    tr = {"devices": {
              "/device:TPU:0": [
                  ("fusion.1", 0.0, 2 * MS),
                  (ar, 2 * MS, 3 * MS),
                  ("all-reduce-done.3", 2.5 * MS, 3.5 * MS),
                  ("all-gather.7", 5 * MS, 6 * MS),
                  ("%fusion.9 = bf16[8] fusion(bf16[8] %all-gather.7)",
                   6 * MS, 7 * MS),
                  ("reduce-scatter.1", 8 * MS, 8.5 * MS),
                  ("all-to-all", 9 * MS, 9.25 * MS),
                  ("collective-permute-start.2", 9.25 * MS, 9.5 * MS),
                  ("collective-permute-done.2", 9.5 * MS, 9.75 * MS),
                  ("module:jit_step(1)", 0.0, 10 * MS)],
              "/device:TPU:1": [("all-reduce.1", 0.0, 1 * MS),
                                ("copy.3", 1 * MS, 2 * MS)],
              "/device:TPU:4": [("all-reduce.1", 0.0, 10 * MS)]},
          "host": [("window", 0.0, 10 * MS)]}
    r = devtrace.reduce(tr, chips=4)
    # TPU:0: [2, 3.5] + [5, 6] + [8, 8.5] + [9, 9.75] = 3.75 ms; TPU:1: 1
    assert r["collective_s"] == pytest.approx((0.00375 + 0.001) / 2)
    assert dict((k, v) for k, v in r["collective_ops"]) == pytest.approx({
        "all-reduce-start.3": 0.0005, "all-reduce-done.3": 0.0005,
        "all-gather.7": 0.0005, "reduce-scatter.1": 0.00025,
        "all-to-all": 0.000125, "collective-permute-start.2": 0.000125,
        "collective-permute-done.2": 0.000125, "all-reduce.1": 0.0005})
    assert r["collective_s"] < r["busy_s"]


def test_recorded_v5e_trace_reads_the_same_for_its_one_chip():
    """The cell's chips filter nothing on a one-chip trace, and a trace
    with no collective reads none."""
    path = Path(__file__).parent / "data" / "v5e_trace.json"
    tr = json.loads(path.read_text())
    every, own = devtrace.reduce(tr), devtrace.reduce(tr, chips=1)
    assert own == every
    # as the reduction read it before it counted collectives
    assert own["busy_s"] == 0.000824941
    assert own["window_s"] == pytest.approx(0.121922407, abs=1e-15)
    assert [k for k, _ in own["device_ops"]] == [
        "fusion", "convolution_tanh_fusion", "copy-done", "dynamic_slice.1",
        "copy-start"]
    assert own["collective_s"] == 0.0 and own["collective_ops"] == []

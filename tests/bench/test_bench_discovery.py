"""The harness finds every cell's files by name, so a new mix or metric is
a new file; and BENCHMARK.json keeps to its own rules."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from conftest import CHIP, harness

ROOT = CHIP.parents[1]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return harness.benchmark()


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"], bench)
        assert (CHIP / "drivers" / f"{cell.traffic['driver']}.py").exists()
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.reader(m["name"]))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        assert cell.limits, w["name"]


def test_benchmark_json_rules(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmarks/chip", "tests/bench"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                  "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    configs = {c["name"] for c in bench["configs"]}
    assert configs == {w["config"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
    for c in bench["configs"]:
        assert (ROOT / c["file"]).exists()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] \
            == c["reduced"]


def _files(base):
    return {p.relative_to(base): p.read_bytes() for p in base.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_mix_and_metric_are_new_files(tmp_path, bench):
    """Copy the harness, add a cell's mix, driver, metrics and limits as
    files, point a new cell at them: the harness finds each, and no file
    it had changes."""
    base = tmp_path / "chip"
    shutil.copytree(CHIP, base, ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(base)
    (base / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"driver": "dummy", "why": "test", "n": 3}))
    (base / "limits" / "gpt.dummy.json").write_text(json.dumps(
        {"dummy_gap": 1.0}))
    (base / "drivers" / "dummy.py").write_text(
        "from harness import Run, Check\n"
        "def run(cell, devices, *, seed, seconds, trace, t0, wrap=None):\n"
        "    n = cell.traffic['n']\n"
        "    return Run(cell=cell, setup_s=0.5, window_s=2.0, attempted=n,\n"
        "               failed=0, records={'n': n},\n"
        "               checks=[Check('dummy_gap', 0.5,\n"
        "                             cell.limits['dummy_gap'])])\n")
    (base / "metrics" / "dummy_rate.py").write_text(
        "def read(run):\n    return run.records['n'] / run.window_s\n")
    (base / "metrics" / "dummy_layer.py").write_text(
        "def read(run):\n    return None\n")
    b = json.loads(json.dumps(bench))
    b["workloads"].append({"name": "gpt.dummy", "config": "gpt",
                           "traffic": "dummy_mix", "chips": 1, "why": "t"})
    b["end_to_end"].append({"name": "dummy_rate", "unit": "1/s",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["gpt.dummy"]})
    b["per_layer"].append({"name": "dummy_layer", "unit": "%",
                           "better": "higher", "source": "device_trace",
                           "layer": "device", "moves": "dummy_rate",
                           "workloads": ["gpt.dummy"]})
    cell = harness.load_cell("gpt.dummy", b, base)
    run = harness.driver(cell, base).run(cell, [], seed=1, seconds=1.0,
                                         trace=False, t0=0.0)
    line = harness.result_line(run, {"platform": "tpu"}, False, base)
    assert line["metrics"]["dummy_rate"]["value"] == 1.5
    assert line["metrics"]["setup_s"]["value"] == 0.5
    assert line["correct"] is True
    assert line["checks"]["dummy_gap"] == {"value": 0.5, "limit": 1.0}
    assert list(line)[-1] == "checks"
    # a reader that finds nothing leaves its metric out of the line
    traced = harness.result_line(run, {"platform": "tpu"}, True, base)
    assert traced["metrics"] == {}
    after = _files(base)
    assert {k: after[k] for k in before} == before


def test_a_cell_without_limits_is_refused(bench):
    """A cell whose limits file is missing does not run with a default."""
    b = json.loads(json.dumps(bench))
    b["workloads"].append({"name": "gpt.nolimits", "config": "gpt",
                           "traffic": "train_8x1024", "chips": 1, "why": "t"})
    with pytest.raises(FileNotFoundError):
        harness.load_cell("gpt.nolimits", b)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload", "gpt.verify",
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr

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


# a family other than dense, as its file would declare it: the keys that
# set the program's sizes include the experts and the attention window
MOE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab",
            "n_experts", "top_k", "moe_d_ff", "window", "pattern")
MOE_FAMILY = (f"SIZE_KEYS = {MOE_KEYS!r}\n"
              "def shapes(c):\n"
              "    return {'w': (c['n_experts'], c['moe_d_ff'])}\n")


def _moe_config(**change):
    """Mixtral-8x7B's registered sizes as a configuration file holds them,
    with ``change`` applied."""
    from repro.models.registry import load_config
    prog = load_config("mixtral-8x7b")
    c = {"name": "dummy_config", "program_config": "mixtral-8x7b",
         "family": "moe", "reduced": []}
    for k in MOE_KEYS:
        v = getattr(prog, k)
        c[k] = list(v) if isinstance(v, tuple) else v
    c.update(change)
    return c


def _harness_with_moe(tmp_path):
    base = tmp_path / "chip"
    shutil.copytree(CHIP, base, ignore=shutil.ignore_patterns("__pycache__"))
    (base / "families" / "moe.py").write_text(MOE_FAMILY)
    return base


def _files(base):
    return {p.relative_to(base): p.read_bytes() for p in base.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_new_mix_and_metric_are_new_files(tmp_path, bench):
    """Copy the harness, add a cell's family (one other than dense, with
    width keys of its own), configuration, mix, driver, metrics and
    limits as files, point a new cell at them: the harness finds each and
    builds the program's configuration from the family's keys, and no file
    it had changes."""
    base = tmp_path / "chip"
    shutil.copytree(CHIP, base, ignore=shutil.ignore_patterns("__pycache__"))
    before = _files(base)
    (base / "families" / "moe.py").write_text(MOE_FAMILY)
    (base / "configs" / "dummy_config.json").write_text(json.dumps(
        _moe_config(n_layers=2, reduced=["n_layers"])))
    (base / "traffic" / "dummy_mix.json").write_text(json.dumps(
        {"driver": "dummy", "why": "test", "n": 3}))
    (base / "limits" / "gpt.dummy.json").write_text(json.dumps(
        {"dummy_gap": 1.0}))
    (base / "drivers" / "dummy.py").write_text(
        "from pathlib import Path\n"
        "import harness\n"
        "from harness import Run, Check\n"
        "BASE = Path(__file__).resolve().parents[1]\n"
        "def run(cell, devices, *, seed, seconds, trace, t0, wrap=None):\n"
        "    cfg = harness.program_config(cell.config, BASE)\n"
        "    f = harness.family(cell.config['family'], BASE)\n"
        "    assert f.shapes(cell.config)['w'] == (cfg.n_experts,\n"
        "                                          cfg.moe_d_ff)\n"
        "    n = cell.traffic['n'] * cfg.n_layers // 2\n"
        "    return Run(cell=cell, setup_s=0.5, window_s=2.0, attempted=n,\n"
        "               failed=0, records={'n': n},\n"
        "               checks=[Check('dummy_gap', 0.5,\n"
        "                             cell.limits['dummy_gap'])])\n")
    (base / "metrics" / "dummy_rate.py").write_text(
        "def read(run):\n    return run.records['n'] / run.window_s\n")
    (base / "metrics" / "dummy_layer.py").write_text(
        "def read(run):\n    return None\n")
    b = json.loads(json.dumps(bench))
    b["configs"].append({"name": "dummy_config", "source": "t",
                         "file": "benchmarks/chip/configs/dummy_config.json",
                         "reduced": [], "why": "t"})
    b["workloads"].append({"name": "gpt.dummy", "config": "dummy_config",
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


def test_a_family_without_a_file_is_refused(tmp_path, bench):
    """A configuration whose family has no ``families/<family>.py`` does
    not load, and the error names the file it looked for."""
    base = tmp_path / "chip"
    shutil.copytree(CHIP, base, ignore=shutil.ignore_patterns("__pycache__"))
    c = json.loads((base / "configs" / "yi-9b.json").read_text())
    (base / "configs" / "yi-9b.json").write_text(
        json.dumps(dict(c, family="no_such_family")))
    want = str(base / "families" / "no_such_family.py")
    with pytest.raises(FileNotFoundError, match=re.escape(want)):
        harness.load_cell("yi-9b.decode", bench, base)
    with pytest.raises(FileNotFoundError, match=re.escape(want)):
        harness.family("no_such_family", base)


@pytest.mark.parametrize("key,value", [
    ("n_experts", 4), ("top_k", 1), ("moe_d_ff", 4096), ("window", 1024),
    ("pattern", ["global"]), ("d_model", 2048)])
def test_a_family_size_that_differs_from_the_program_is_refused(
        tmp_path, key, value):
    """Every key the family's file names as a size is compared with the
    program's registered configuration: one that differs, and is not
    listed as reduced, is refused by name."""
    base = _harness_with_moe(tmp_path)
    assert harness.program_config(_moe_config(), base).n_experts == 8
    with pytest.raises(ValueError, match=f"`{key}`"):
        harness.program_config(_moe_config(**{key: value}), base)


def test_a_missing_size_or_another_family_is_refused(tmp_path):
    base = _harness_with_moe(tmp_path)
    c = _moe_config()
    del c["moe_d_ff"]
    with pytest.raises(KeyError, match="moe_d_ff"):
        harness.program_config(c, base)
    with pytest.raises(ValueError, match="`moe` in the program"):
        harness.program_config(dict(_moe_config(), family="dense"), base)


def test_every_configured_family_has_its_file(bench):
    for w in bench["workloads"]:
        c = harness.load_cell(w["name"], bench).config
        if "family" in c:
            f = harness.family(c["family"])
            for name in ("shapes", "weights", "to_program", "from_program",
                         "served_gaps", "control_gaps", "train"):
                assert callable(getattr(f, name)), (c["family"], name)
            assert set(f.SIZE_KEYS) <= set(c), c["name"]


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(CHIP / "run.py"), "--workload", "gpt.verify",
         "--seed", str(2**31 + 9), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr

"""The decode harness on a (1, 4) mesh: the weights drawn straight into
four devices' shardings, a tiny dense cell decoded tensor parallel and
checked against the reference split over the devices, and the shares of
a peak divided by the cell's chips.

The four devices are the CPU's, made by XLA's flag in a subprocess of its
own, as ``test_run_without_a_tpu_exits_nonzero_and_prints_no_result``
runs one: the flag has to be set before JAX starts."""
import json
import os
import subprocess
import sys

import pytest

from conftest import CHIP, harness, tiny_cell

ROOT = CHIP.parents[1]

SCRIPT = r"""
import json, sys, time
sys.path.insert(0, %(tests)r)
from conftest import TINY_DENSE, harness, tiny_cell
import jax
import numpy as np
import faults
import weights as W

devices = jax.devices("cpu")
assert len(devices) == 4, devices
out = {}

# the draw: one device, spread over four, and into the program's shardings
c = dict(TINY_DENSE, n_heads=8, n_kv_heads=4, head_dim=8)
one = W.dense_weights(c, 2**31 + 5, "bfloat16", devices[0])
cell = tiny_cell("yi-9b.decode", {"batch": 4, "prompt": 8, "max_seq": 64,
                                  "sample": 2, "ref_bucket": 32,
                                  "mesh": [1, 4]},
                 config=c, limits={"served_gap": 0.04})
from repro.launch.mesh import rules_for_config
from repro.launch.steps import build_decode
from repro.models.config import InputShape
cfg = harness.program_config(c)
mesh = harness.mesh_of(devices, [1, 4])
_, _, sh, _ = build_decode(cfg, InputShape("t", 64, 4, "decode"), mesh,
                           rules_for_config(cfg, mesh))
for name, where in (("spread", W.spread(W.dense_shapes(c), devices)),
                    ("program", W.from_program(sh[0]))):
    got = W.dense_weights(c, 2**31 + 5, "bfloat16", where)
    out["draw_" + name] = {
        "equal": all(np.array_equal(np.asarray(one[k]), np.asarray(got[k]))
                     for k in one),
        "split": sorted(k for k in got
                        if got[k].addressable_shards[0].data.shape
                        != got[k].shape)}

# no split array goes through the host on its way to the reference's
# placement: the gather is the devices' own
import jax._src.array as jax_array


def through_the_host(*args, **kwargs):
    raise AssertionError("a split array was resharded through the host")


jax_array.shard_sharded_device_array_slow_path = through_the_host

# the cell, sound and with every 16th token altered
for label, wrap in (("sound", None), ("altered", faults.decode_token_altered)):
    run = harness.driver(cell).run(cell, devices, seed=2**31 + 77,
                                   seconds=0.6, trace=False,
                                   t0=time.perf_counter(), wrap=wrap)
    line = harness.result_line(run, {"platform": "cpu"}, False)
    out[label] = {"correct": line["correct"], "checks": line["checks"],
                  "answer_len": run.records["answer_len"]}
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src"), str(CHIP)]))
    p = subprocess.run(
        [sys.executable, "-c", SCRIPT % {"tests": str(ROOT / "tests"
                                                      / "bench")}],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("where", ["spread", "program"])
def test_sharded_draw_equals_the_one_device_draw(four_devices, where):
    got = four_devices["draw_" + where]
    assert got["equal"]
    # the draw really is split: the blocks' matrices lie in quarters
    assert {"wq", "wk", "wv", "wo", "wg", "wu", "wd"} <= set(got["split"])


def test_tensor_parallel_decode_is_correct(four_devices):
    got = four_devices["sound"]
    assert got["answer_len"] > 16
    assert got["correct"], got["checks"]


def test_tensor_parallel_decode_with_altered_tokens_fails(four_devices):
    got = four_devices["altered"]
    assert got["answer_len"] > 16
    assert got["correct"] is False, got["checks"]


@pytest.mark.parametrize("metric", ["mfu.decode", "hbm_share.decode"])
def test_shares_divide_by_the_cells_chips(metric):
    cell = tiny_cell("yi-9b.decode", {})
    cell.config = json.loads((CHIP / "configs" / "yi-9b.json").read_text())
    run = harness.Run(cell=cell, setup_s=1.0, window_s=2.0, attempted=64,
                      failed=0, records={"positions": [600, 601],
                                         "steps": 2, "tokens": 64},
                      device_kind="TPU v5 lite")
    read = harness.reader(metric)
    cell.chips = 1
    one = read(run)
    cell.chips = 4
    assert one > 0
    assert read(run) == pytest.approx(one / 4, rel=1e-12)

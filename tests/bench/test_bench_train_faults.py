"""Training: a run with the timed path broken underneath reads ``correct``
false, and so does the fp8 control; a sound run reads true.  A tiny model
on the CPU, handed the CPU device so that the chip check is skipped.

The chip's limits (``limits/gpt.train.json``) are set from readings at
the cell's own size; a model of width 64 and vocabulary 256 rounds more
coarsely, so these tests hold it to limits set the same way from its own
readings (on the CPU: sound 2.1e-4 / 5.9e-4 / 9.3e-3 for loss / grad / change; fp8
control 3.1e-3 / 4.3e-3 / 4.8e-2; half batch 7.9e-3 / 6.6e-2 / 0.25)."""
import time

import pytest

from conftest import harness, tiny_cell

import faults  # noqa: E402

SEED = 2**31 + 77

TRAIN = {"batch": 2, "seq": 32, "pool": 4, "ref_rows_per_block": 1}


LIMITS = {"loss_gap": 1e-3, "grad_gap": 2e-3, "change_gap": 2.5e-2}
CELL = tiny_cell("gpt.train", TRAIN, limits=LIMITS)
DRV = harness.driver(CELL)


@pytest.fixture(scope="module", autouse=True)
def reference_once(cpu_devices_module):
    """The reference's readings depend only on the seed: compute them once
    for every run of this file."""
    orig = DRV.reference_readings
    ref = orig(CELL, SEED, cpu_devices_module)
    DRV.reference_readings = lambda c, s, d, fp8=False: \
        orig(c, s, d, fp8=True) if fp8 else ref
    yield ref
    DRV.reference_readings = orig


def _run(devices, wrap=None):
    run = DRV.run(CELL, devices, seed=SEED, seconds=0.3, trace=False,
                  t0=time.perf_counter(), wrap=wrap)
    run.device_kind = "TPU v5 lite"
    return run, harness.result_line(run, {"platform": "cpu"}, False)


def test_train_sound_run_is_correct(cpu_devices):
    run, line = _run(cpu_devices)
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_train_fault_is_caught(cpu_devices, fault):
    run, line = _run(cpu_devices, faults.TRAIN[fault])
    assert line["correct"] is False, line["checks"]


def test_train_fp8_control_is_caught(cpu_devices, reference_once):
    ctl = DRV.reference_readings(CELL, SEED, cpu_devices, fp8=True)
    got = DRV.compare(ctl["losses"], ctl["grad"], ctl["change"],
                      reference_once)
    assert any(got[k] > CELL.limits[k]
               for k in ("loss_gap", "grad_gap", "change_gap")), got

"""Serving and verdicts: a run with the timed path broken underneath reads
``correct`` false, and so does the control; a sound run reads true.  The
drivers are handed the CPU device, so the chip check is skipped.

Decoding runs a tiny model, held to a limit set from its own readings (my
CPU run: sound 0.0057, fp8 control 0.12, altered token 4.4, unchanged
cache 1.7), as the chip's limit is from readings at the cell's size.  The
verdict cell runs at its own size: GPT's verdicts take a fraction of a
second on the CPU, and its limit (0 wrong verdicts) is exact."""
import time

import numpy as np
import pytest

from conftest import harness, tiny_cell

import faults  # noqa: E402

SEED = 2**31 + 77

DECODE = {"batch": 4, "prompt": 8, "max_seq": 64, "sample": 2,
          "ref_bucket": 32}


def _run(cell, devices, wrap=None, seconds=0.3, **kw):
    run = harness.driver(cell).run(cell, devices, seed=SEED, seconds=seconds,
                                   trace=False, t0=time.perf_counter(),
                                   wrap=wrap, **kw)
    run.device_kind = "TPU v5 lite"
    return run, harness.result_line(run, {"platform": "cpu"}, False)


def _decode_cell():
    return tiny_cell("yi-9b.decode", DECODE, limits={"served_gap": 0.04})


def test_decode_sound_run_is_correct(cpu_devices):
    run, line = _run(_decode_cell(), cpu_devices)
    assert run.records["answer_len"] > 8
    assert line["correct"], line["checks"]


@pytest.mark.parametrize("fault", sorted(faults.DECODE))
def test_decode_fault_is_caught(cpu_devices, fault):
    run, line = _run(_decode_cell(), cpu_devices, faults.DECODE[fault])
    assert line["correct"] is False, line["checks"]


def test_decode_fp8_control_is_caught(cpu_devices):
    cell = _decode_cell()
    run, _ = _run(cell, cpu_devices)
    drv = harness.driver(cell)
    import weights as W
    t = cell.traffic
    prompts = np.asarray(W.token_stream(SEED, 0, (t["batch"], t["prompt"]),
                                        cell.config["vocab"]))
    rows = run.records["rows"]
    gap = drv.served_gap(cell, SEED, cpu_devices, prompts[rows],
                         run.records["served"][rows], fp8=True)
    assert gap > cell.limits["served_gap"]


def test_verify_answer_altered_is_caught(cpu_devices):
    cell = harness.load_cell("gpt.verify")
    run, line = _run(cell, cpu_devices, faults.verify_flipped, seconds=0.5)
    assert run.attempted >= 5
    assert line["correct"] is False


def test_verify_small_budget_control_is_caught(cpu_devices):
    import calibrate
    cell = harness.load_cell("gpt.verify")
    run, line = _run(cell, cpu_devices, seconds=0.5, engine_opts={
        "max_nodes": calibrate.CONTROL_MAX_NODES})
    assert line["correct"] is False

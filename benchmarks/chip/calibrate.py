"""Readings that the limits of ``limits/<cell>.json`` are set from.  Not run by
the benchmark's own runs.

    python3 benchmarks/chip/calibrate.py --workload yi-9b.decode \
        --seeds 1,2,3 --control-seeds 1,2,3 --fault-seeds 1,2,3 \
        --seconds 30 --out calib_decode.jsonl

For each seed, in one process: the program's own reading of every number
that decides ``correct`` (a full run of the cell with a window of
``--seconds``, or, for training, the checked steps alone); the control's
(the reference computed in fp8 in the program's place, or, for a
checker, the program with a small e-graph budget); and each fault of
``faults.py`` planted under the timed path.  One JSON line per reading.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import harness  # noqa: E402
import faults  # noqa: E402

CONTROL_MAX_NODES = 600


def _seeds(s):
    return [int(x) for x in s.split(",") if x]


def _emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")


def _train(cell, devices, args, out):
    drv = harness.driver(cell)
    for seed in _seeds(args.seeds):
        t = time.perf_counter()
        run = drv.run(cell, devices, seed=seed, seconds=args.seconds,
                      trace=False, t0=t)
        _emit(out, {"cell": cell.name, "kind": "program", "seed": seed,
                    "readings": run.records["readings"],
                    "setup_s": run.setup_s, "steps": run.records["steps"],
                    "window_s": run.window_s, "peak": run.peak_bytes})
    for seed in _seeds(args.control_seeds):
        ref = drv.reference_readings(cell, seed, devices)
        ctl = drv.reference_readings(cell, seed, devices, fp8=True)
        _emit(out, {"cell": cell.name, "kind": "control_fp8", "seed": seed,
                    "readings": drv.compare(ctl["losses"], ctl["grad"],
                                            ctl["change"], ref)})
    for seed in _seeds(args.fault_seeds):
        for name in ("half_batch",):
            run = drv.run(cell, devices, seed=seed, seconds=0.0,
                          trace=False, t0=time.perf_counter(),
                          wrap=faults.TRAIN[name])
            _emit(out, {"cell": cell.name, "kind": f"fault_{name}",
                        "seed": seed, "readings": run.records["readings"]})


def _decode(cell, devices, args, out):
    import numpy as np
    drv = harness.driver(cell)
    t = cell.traffic
    for seed in _seeds(args.seeds):
        run = drv.run(cell, devices, seed=seed, seconds=args.seconds,
                      trace=False, t0=time.perf_counter())
        rec = {"cell": cell.name, "kind": "program", "seed": seed,
               "served_gap": run.checks[0].value, "setup_s": run.setup_s,
               "steps": run.records["steps"], "window_s": run.window_s,
               "peak": run.peak_bytes}
        if seed in _seeds(args.control_seeds):
            # the control at the same prompts and served tokens
            prompts = np.asarray(__import__("weights").token_stream(
                seed, 0, (t["batch"], t["prompt"]), cell.config["vocab"]))
            rows = run.records["rows"]
            rec["control_gap"] = drv.served_gap(
                cell, seed, devices, prompts[rows],
                run.records["served"][rows], fp8=True)
        _emit(out, rec)
    for seed in _seeds(args.fault_seeds):
        for name, wrap in faults.DECODE.items():
            run = drv.run(cell, devices, seed=seed, seconds=args.seconds,
                          trace=False, t0=time.perf_counter(), wrap=wrap)
            _emit(out, {"cell": cell.name, "kind": f"fault_{name}",
                        "seed": seed, "served_gap": run.checks[0].value})


def _verify(cell, devices, args, out):
    drv = harness.driver(cell)
    for kind, seeds, kw in (
            ("program", args.seeds, {}),
            ("control_small_budget", args.control_seeds,
             {"engine_opts": {"max_nodes": CONTROL_MAX_NODES}}),
            ("fault_answer_altered", args.fault_seeds,
             {"wrap": faults.verify_flipped})):
        for seed in _seeds(seeds):
            run = drv.run(cell, devices, seed=seed, seconds=args.seconds,
                          trace=False, t0=time.perf_counter(), **kw)
            _emit(out, {"cell": cell.name, "kind": kind, "seed": seed,
                        "verdicts_wrong": run.checks[0].value,
                        "verdicts": len(run.records["verdicts"]),
                        "window_s": run.window_s, "setup_s": run.setup_s})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"[calibrate] {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    {"train": _train, "decode": _decode, "verify": _verify}[
        cell.traffic["driver"]](cell, devices, args, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload gpt.train --seed 7 \
        --seconds 30 --trace 0

Loads, warms up every shape the cell uses (all of it ``setup_s``, from
process start), measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON line as the
last line of standard output.  With ``--trace 1`` the window runs under
the profiler and the line carries the per-layer metrics instead of the
end-to-end ones.  Without a TPU, or with fewer chips than the cell asks
for, it exits 2 and prints no result.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    harness.mark("start")
    try:
        devices = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    harness.mark("devices")
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    harness.mark("compile_cache")

    run = harness.driver(cell).run(cell, devices, seed=args.seed,
                                   seconds=args.seconds,
                                   trace=bool(args.trace), t0=T0)
    run.device_kind = devices[0].device_kind
    phases = harness.setup_phases(T0)
    line = harness.result_line(run, harness.describe(devices),
                               bool(args.trace), phases=phases)
    print("setup phases: " + json.dumps(phases), file=sys.stderr)
    harness.print_checks(run)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

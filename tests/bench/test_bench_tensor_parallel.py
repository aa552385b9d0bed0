"""The four-chip cell ``yi-9b.decode.tp4`` and its per-layer metric
``collective_share.decode``, on the CPU: the cell's files load by name, and
the metric reads a synthetic trace."""
import pytest

from conftest import harness, tiny_cell

import devtrace  # noqa: E402

MS = 1_000_000.0          # nanoseconds


def test_the_tensor_parallel_cell_is_yi9b_whole_on_four_chips():
    """``yi-9b.decode.tp4`` loads by name with four chips, and its
    configuration is the program's registered Yi-9B with nothing cut:
    all 48 layers at published widths, split over the mix's (1, 4) mesh."""
    from repro.models.registry import load_config
    bench = harness.benchmark()
    cell = harness.load_cell("yi-9b.decode.tp4", bench)
    assert cell.chips == 4
    assert cell.config["reduced"] == []
    assert harness.program_config(cell.config) == load_config("yi-9b")
    assert cell.traffic["mesh"] == [1, 4]
    one = harness.load_cell("yi-9b.decode", bench).traffic
    same = [k for k in one if k not in ("mesh", "why")]
    assert {k: cell.traffic[k] for k in same} == {k: one[k] for k in same}
    assert set(cell.traffic) == set(one)
    names = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert {"decode_tokens_per_s", "decode_step_p95_ms", "setup_s",
            "mfu.decode", "hbm_share.decode", "idle_share.decode",
            "collective_share.decode"} == names
    assert set(cell.limits) == {"served_gap"}


def test_collective_share_reads_collective_over_busy_time():
    """``collective_share.decode``: the collective time of the reduced
    trace over its busy time, in percent; nothing without a trace."""
    read = harness.reader("collective_share.decode")
    cell = tiny_cell("yi-9b.decode.tp4", {})
    run = harness.Run(cell=cell, setup_s=1.0, window_s=2.0, attempted=1,
                      failed=0)
    assert read(run) is None
    ar = "all-reduce.5"
    tr = {"devices": {
              "/device:TPU:0": [("fusion.1", 0.0, 3 * MS),
                                (ar, 3 * MS, 4 * MS)],
              "/device:TPU:1": [("fusion.1", 0.0, 2 * MS),
                                (ar, 2 * MS, 4 * MS)]},
          "host": [("window", 0.0, 8 * MS)]}
    run.trace = devtrace.reduce(tr, chips=2)
    # busy 4 ms on each chip; collectives 1 and 2 ms: 1.5 of 4 ms
    assert run.trace["busy_s"] == pytest.approx(0.004)
    assert read(run) == pytest.approx(37.5)
    run.trace = devtrace.reduce({"devices": {"/device:TPU:0": [
        ("fusion.1", 0.0, 2 * MS)]}, "host": [("window", 0.0, 4 * MS)]})
    assert read(run) == 0.0

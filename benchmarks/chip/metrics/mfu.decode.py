"""The whole decode step's share of the chip's bf16 peak, in percent: the
operations each step needs at its position, summed over the window's
steps, over the window, over the peak."""
import harness


def read(run):
    if run.cell.traffic["driver"] != "decode" or not run.records["steps"]:
        return None
    c, t = run.cell.config, run.cell.traffic
    n = harness.counts(c["family"])
    flops = sum(n.decode_flops(c, t["batch"], p)
                for p in run.records["positions"])
    peak = harness.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / run.window_s / peak

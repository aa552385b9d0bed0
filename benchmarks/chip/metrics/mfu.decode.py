"""The whole decode step's share of the cell's bf16 peak, in percent: the
operations each step needs at its position, summed over the window's
steps, over the window, over the cell's chips times one chip's peak."""
import harness


def read(run):
    if not run.records.get("positions"):
        return None
    c, t = run.cell.config, run.cell.traffic
    n = harness.counts(c["family"])
    flops = sum(n.decode_flops(c, t["batch"], p)
                for p in run.records["positions"])
    peak = run.cell.chips * harness.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / run.window_s / peak

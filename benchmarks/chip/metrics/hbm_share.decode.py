"""The decode step's share of the cell's HBM bandwidth, in percent: the
bytes each step needs (every weight, the K/V cache up to its position
only), summed over the window's steps, over the window, over the cell's
chips times one chip's peak."""
import harness


def read(run):
    if not run.records.get("positions"):
        return None
    c, t = run.cell.config, run.cell.traffic
    n = harness.counts(c["family"])
    need = sum(n.decode_bytes(c, t["batch"], p)
               for p in run.records["positions"])
    peak = run.cell.chips * harness.peaks(run.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / run.window_s / peak

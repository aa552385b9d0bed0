"""The whole training step's share of the chip's bf16 peak, in percent:
the forward and backward operations the model needs per step (no
recomputation counted) times steps, over the window, over the peak."""
import harness


def read(run):
    if run.cell.traffic["driver"] != "train" or not run.records["steps"]:
        return None
    c, t = run.cell.config, run.cell.traffic
    flops = harness.counts(c["family"]).train_flops(c, t["batch"], t["seq"])
    peak = harness.peaks(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops * run.records["steps"] / run.window_s / peak

"""The 95th percentile of the gap between tokens: the host's time from one
step's dispatch to its tokens read back, over every step of the window.
Each step ends in reading its tokens back, as a server streams them."""
import numpy as np


def read(run):
    if not run.records.get("step_s"):
        return None
    return float(np.percentile(run.records["step_s"], 95)) * 1e3

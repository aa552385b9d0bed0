"""Training tokens per second: every token of every step over the whole
window."""


def read(run):
    if run.cell.traffic["driver"] != "train" or not run.records["steps"]:
        return None
    return run.records["tokens"] / run.window_s

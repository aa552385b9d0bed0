"""Percent of the device's busy time in the traced window in which a
collective ran (all-reduce, all-gather, reduce-scatter, all-to-all,
collective-permute and their asynchronous halves: ``devtrace.reduce``'s
``collective_s``), both averaged over the cell's chips."""


def read(run):
    if run.trace is None or not run.trace.get("busy_s"):
        return None
    return 100.0 * run.trace["collective_s"] / run.trace["busy_s"]

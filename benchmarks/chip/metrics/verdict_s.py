"""Seconds per cold verdict: the whole window over the verdicts it
completed, so a stall counts."""


def read(run):
    n = len(run.records.get("verdicts", ()))
    return run.window_s / n if n else None

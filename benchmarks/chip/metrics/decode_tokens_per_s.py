"""Generated tokens per second: every token of every step over the whole
window."""


def read(run):
    if not run.records.get("positions"):
        return None
    return run.records["tokens"] / run.window_s

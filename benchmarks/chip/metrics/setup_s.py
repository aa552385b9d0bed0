"""Seconds from process start to the start of the measured window:
imports, weights, compilation (or the compile cache) and warm-up."""


def read(run):
    return run.setup_s

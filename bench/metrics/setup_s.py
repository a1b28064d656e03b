"""Seconds from process start to the first round of the window: imports,
device start, weights, compile or cache load, and the checked warm-up rounds."""


def read(run):
    return run.setup_s

"""Seconds of XLA compiles and persistent-cache loads during set-up, from the
program's compile sentinel (``repro.obs.sentinels``)."""


def read(run):
    return run.compile_s

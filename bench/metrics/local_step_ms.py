"""Device self time under the program's ``local_step`` scope (the whole local
SGD step: forward, backward and the local update) per computed local step,
in ms, from a traced window (``bench/scopes.py``)."""

from bench.scopes import read_metric


def read(run):
    return read_metric(run, "local_step_ms")

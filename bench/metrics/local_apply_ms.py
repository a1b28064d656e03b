"""Device self time under the ``local_apply`` scope (the local optimizer
applying a step's gradient) per computed local step, in ms, from a traced
window (``bench/scopes.py``)."""

from bench.scopes import read_metric


def read(run):
    return read_metric(run, "local_apply_ms")

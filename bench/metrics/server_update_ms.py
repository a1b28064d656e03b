"""Device self time under the ``server_update`` scope (the server optimizer
step) per round, in ms, from a traced window (``bench/scopes.py``)."""

from bench.scopes import read_metric


def read(run):
    return read_metric(run, "server_update_ms")

"""Device self time under the ``client_delta`` and ``accumulate`` scopes (each
client's weighted delta added to the fp32 aggregate) per round, in ms, from
a traced window (``bench/scopes.py``)."""

from bench.scopes import read_metric


def read(run):
    return read_metric(run, "accumulate_ms")

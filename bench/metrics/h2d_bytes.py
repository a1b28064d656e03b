"""Bytes the program hands the device per round, the ``bytes`` of its
``data/to_device`` spans in a traced window (``bench/scopes.py``)."""

from bench.scopes import read_metric


def read(run):
    return read_metric(run, "h2d_bytes")

"""Device-idle time inside the program's ``data/*`` spans (cohort plan,
materialisation, transfer) per round, in ms: how long the chip waits for the
data plane, from a traced window (``bench/scopes.py``)."""

from bench.scopes import read_metric


def read(run):
    return read_metric(run, "data_wait_ms")

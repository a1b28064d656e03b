"""Device self time under the ``lm_head`` scope (final norm, logits, cross
entropy and their backward) per computed local step, in ms, from a traced
window (``bench/scopes.py``)."""

from bench.scopes import read_metric


def read(run):
    return read_metric(run, "lm_head_ms")

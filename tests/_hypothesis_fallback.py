"""A minimal stand-in for the ``hypothesis`` API used by this test suite.

The real package is declared in the ``[test]`` extra (pyproject.toml) and is
used when installed; this fallback keeps the property-based test modules
collectable and *running* in environments without it (e.g. hermetic CPU
images).  It draws ``max_examples`` pseudo-random examples per test from a
deterministic per-test seed — no shrinking, no database, just coverage.

Supported subset: ``given`` (kwargs form), ``settings(max_examples, deadline)``,
``assume`` (a failed one skips the example) and the strategies ``integers``, ``floats``, ``booleans``, ``lists``,
``sampled_from``, ``just``, plus ``Strategy.map/filter``.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

_DEFAULT_MAX_EXAMPLES = 25
_SETTINGS_ATTR = "_fallback_settings"


@dataclass
class Strategy:
    draw: Callable[[np.random.Generator], Any]

    def map(self, f):
        return Strategy(lambda rng: f(self.draw(rng)))

    def filter(self, pred, _tries: int = 1000):
        def draw(rng):
            for _ in range(_tries):
                x = self.draw(rng)
                if pred(x):
                    return x
            raise ValueError("filter predicate never satisfied")

        return Strategy(draw)


def integers(min_value: int, max_value: int) -> Strategy:
    return Strategy(lambda rng: int(rng.integers(min_value, max_value + 1)))


def floats(min_value: float, max_value: float, **_kw) -> Strategy:
    return Strategy(lambda rng: float(rng.uniform(min_value, max_value)))


def booleans() -> Strategy:
    return Strategy(lambda rng: bool(rng.integers(0, 2)))


def lists(elements: Strategy, *, min_size: int = 0, max_size: int = 10) -> Strategy:
    return Strategy(
        lambda rng: [elements.draw(rng)
                     for _ in range(int(rng.integers(min_size, max_size + 1)))]
    )


def sampled_from(seq) -> Strategy:
    seq = list(seq)
    return Strategy(lambda rng: seq[int(rng.integers(0, len(seq)))])


def just(value) -> Strategy:
    return Strategy(lambda rng: value)


def settings(max_examples: int = _DEFAULT_MAX_EXAMPLES, deadline=None, **_kw):
    def deco(fn):
        setattr(fn, _SETTINGS_ATTR, {"max_examples": max_examples})
        return fn

    return deco


class _Unsatisfied(Exception):
    """Raised by :func:`assume`; ``given`` drops the example."""


def assume(condition) -> bool:
    if not condition:
        raise _Unsatisfied
    return True


def given(**strategies: Strategy):
    def deco(fn):
        # NB: no functools.wraps — the wrapper must present a zero-arg
        # signature or pytest treats the drawn parameters as fixtures.
        def wrapper():
            cfg = getattr(wrapper, _SETTINGS_ATTR, None) or getattr(
                fn, _SETTINGS_ATTR, {"max_examples": _DEFAULT_MAX_EXAMPLES})
            seed = zlib.crc32(fn.__qualname__.encode())
            rng = np.random.default_rng(seed)
            for _ in range(cfg["max_examples"]):
                drawn = {k: s.draw(rng) for k, s in strategies.items()}
                try:
                    fn(**drawn)
                except _Unsatisfied:
                    continue

        for attr in ("__name__", "__qualname__", "__doc__", "__module__"):
            setattr(wrapper, attr, getattr(fn, attr))
        return wrapper

    return deco

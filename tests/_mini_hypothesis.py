"""Seeded-random fallback for `hypothesis` when it is not installed.

Implements exactly the subset this suite uses — ``given``, ``settings``
and the ``integers / floats / lists / tuples / builds / sampled_from``
strategies — by
degrading each ``@given`` property test to ``max_examples`` seeded-random
example runs.  Weaker than real hypothesis (no shrinking, no failure
database, no edge-case bias) but it keeps the property tests collectible
and meaningful on minimal CI images.  ``pip install -r
requirements-dev.txt`` to run the real thing; the test modules prefer it
automatically when importable.
"""
from __future__ import annotations

import inspect
import zlib
from typing import Callable

import numpy as np


class _Strategy:
    """A strategy is just a draw function rng -> value."""

    def __init__(self, draw: Callable):
        self.draw = draw


class strategies:
    @staticmethod
    def integers(min_value: int, max_value: int) -> _Strategy:
        return _Strategy(lambda r: int(r.integers(min_value, max_value + 1)))

    @staticmethod
    def floats(min_value: float, max_value: float, allow_nan: bool = False,
               **_kw) -> _Strategy:
        return _Strategy(lambda r: float(r.uniform(min_value, max_value)))

    @staticmethod
    def lists(elements: _Strategy, min_size: int = 0, max_size: int = None,
              unique: bool = False) -> _Strategy:
        hi = max_size if max_size is not None else min_size + 8

        def draw(r):
            n = int(r.integers(min_size, hi + 1))
            out, seen, tries = [], set(), 0
            while len(out) < n and tries < 100 * (n + 1):
                v = elements.draw(r)
                tries += 1
                if unique:
                    if v in seen:
                        continue
                    seen.add(v)
                out.append(v)
            return out

        return _Strategy(draw)

    @staticmethod
    def tuples(*ss: _Strategy) -> _Strategy:
        return _Strategy(lambda r: tuple(s.draw(r) for s in ss))

    @staticmethod
    def builds(target: Callable, *ss: _Strategy) -> _Strategy:
        return _Strategy(lambda r: target(*(s.draw(r) for s in ss)))

    @staticmethod
    def sampled_from(elements) -> _Strategy:
        pool = list(elements)
        return _Strategy(lambda r: pool[int(r.integers(0, len(pool)))])


def settings(max_examples: int = 20, deadline=None, **_kw):
    def deco(f):
        f._mini_max_examples = max_examples
        return f

    return deco


def given(*ss: _Strategy):
    def deco(f):
        n = getattr(f, "_mini_max_examples", 20)
        # like hypothesis, the strategies fill the rightmost arguments; the
        # leading ones stay for pytest (parametrize, fixtures)
        params = list(inspect.signature(f).parameters.values())
        lead = params[:len(params) - len(ss)]

        def runner(**kw):
            # deterministic per-test seed so failures reproduce
            rng = np.random.default_rng(zlib.crc32(f.__name__.encode()))
            for _ in range(n):
                f(*(kw[p.name] for p in lead), *(s.draw(rng) for s in ss))

        # only the leading arguments in the signature, so pytest doesn't
        # mistake the generated ones for fixtures
        runner.__signature__ = inspect.Signature(lead)
        runner.__name__ = f.__name__
        runner.__doc__ = f.__doc__
        return runner

    return deco

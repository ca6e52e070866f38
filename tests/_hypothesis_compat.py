"""Seeded-example fallback for the slice of hypothesis this suite uses.

The container does not ship ``hypothesis`` (it is an *optional* dev
dependency, see requirements-dev.txt).  Property tests import through this
shim::

    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        from _hypothesis_compat import given, settings, st

With the real package installed the shim is bypassed entirely.  Without it,
``@given`` degrades to a deterministic sweep: each strategy draws from one
seeded ``random.Random`` stream, and the test body runs ``max_examples``
times (capped by ``HYPOTHESIS_COMPAT_MAX_EXAMPLES``, default 25, so model-
heavy suites stay fast), after every explicit ``@example``.  No shrinking,
no database — just seeded coverage of the same parameter space.
"""

from __future__ import annotations

import os
import random

_SEED = 0xC0FFEE
_DEFAULT_CAP = int(os.environ.get("HYPOTHESIS_COMPAT_MAX_EXAMPLES", "25"))


class _Strategy:
    def __init__(self, draw):
        self._draw = draw

    def example(self, rng: random.Random):
        return self._draw(rng)


class _Strategies:
    @staticmethod
    def integers(min_value: int, max_value: int) -> _Strategy:
        return _Strategy(lambda rng: rng.randint(min_value, max_value))

    @staticmethod
    def sampled_from(options) -> _Strategy:
        options = list(options)
        return _Strategy(lambda rng: rng.choice(options))

    @staticmethod
    def booleans() -> _Strategy:
        return _Strategy(lambda rng: rng.random() < 0.5)

    @staticmethod
    def tuples(*elems: _Strategy) -> _Strategy:
        return _Strategy(lambda rng: tuple(e.example(rng) for e in elems))

    @staticmethod
    def lists(elem: _Strategy, min_size: int = 0, max_size: int = 10) -> _Strategy:
        def draw(rng):
            n = rng.randint(min_size, max_size)
            return [elem.example(rng) for _ in range(n)]

        return _Strategy(draw)

    @staticmethod
    def floats(min_value: float, max_value: float) -> _Strategy:
        return _Strategy(lambda rng: rng.uniform(min_value, max_value))


st = _Strategies()


def settings(*, max_examples: int = 20, deadline=None, **_):
    """Records ``max_examples`` on the decorated function/runner (works in
    either decorator order relative to ``@given``, like the real package)."""

    def deco(fn):
        fn._compat_settings = {"max_examples": max_examples}
        return fn

    return deco


def example(**kwargs):
    """Records one explicit example, run before the seeded sweep (either
    decorator order relative to ``@given``, like the real package)."""

    def deco(fn):
        fn._compat_examples = [kwargs] + getattr(fn, "_compat_examples", [])
        return fn

    return deco


def given(**strategies):
    def deco(fn):
        def runner():
            conf = getattr(runner, "_compat_settings", None) or getattr(
                fn, "_compat_settings", {}
            )
            explicit = getattr(runner, "_compat_examples", []) + getattr(
                fn, "_compat_examples", []
            )
            n = min(conf.get("max_examples", 20), _DEFAULT_CAP)
            rng = random.Random(_SEED)
            for i in range(len(explicit) + n):
                if i < len(explicit):
                    kwargs = explicit[i]
                else:
                    kwargs = {name: s.example(rng) for name, s in strategies.items()}
                try:
                    fn(**kwargs)
                except Exception as e:
                    raise AssertionError(
                        f"falsifying example ({i + 1}/{len(explicit) + n}): "
                        f"{fn.__name__}(**{kwargs!r})"
                    ) from e

        runner.__name__ = fn.__name__
        runner.__doc__ = fn.__doc__
        runner.__module__ = fn.__module__
        return runner

    return deco

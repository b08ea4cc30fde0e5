"""Offline stand-in for ``hypothesis`` (registered by ``conftest.py``).

The container has no network access and no ``hypothesis`` wheel; without
it five tier-1 test modules fail at *collection*.  This stub implements
the tiny slice of the API those modules use — ``given``, ``settings``
and the ``integers`` / ``floats`` / ``tuples`` / ``lists`` / ``sets`` /
``dictionaries`` / ``data`` strategies —
drawing a small, deterministic set of examples per test (seeded PRNG, so
failures reproduce).  It is only installed when the real package is
missing; with ``hypothesis`` available nothing here is imported.
"""
from __future__ import annotations

import functools
import inspect
import random
import sys
import types

#: Deterministic examples per @given test.  Real hypothesis shrinks and
#: explores; the stub just smoke-runs a handful of varied draws.
MAX_EXAMPLES = 5


class _Strategy:
    def __init__(self, draw):
        self._draw = draw

    def example(self, rng: random.Random):
        return self._draw(rng)


def integers(min_value: int, max_value: int) -> _Strategy:
    return _Strategy(lambda rng: rng.randint(min_value, max_value))


def floats(min_value: float, max_value: float, **_kw) -> _Strategy:
    return _Strategy(lambda rng: rng.uniform(min_value, max_value))


def booleans() -> _Strategy:
    return _Strategy(lambda rng: bool(rng.getrandbits(1)))


def sampled_from(seq) -> _Strategy:
    items = list(seq)
    return _Strategy(lambda rng: items[rng.randrange(len(items))])


def tuples(*elements: _Strategy) -> _Strategy:
    return _Strategy(lambda rng: tuple(e.example(rng) for e in elements))


def lists(elements: _Strategy, *, min_size: int = 0,
          max_size: int = 10, **_kw) -> _Strategy:
    def draw(rng):
        n = rng.randint(min_size, max_size)
        return [elements.example(rng) for _ in range(n)]
    return _Strategy(draw)


def sets(elements: _Strategy, *, min_size: int = 0,
         max_size: int = 10, **_kw) -> _Strategy:
    def draw(rng):
        n = rng.randint(min_size, max_size)
        out = set()
        for _ in range(8 * max(n, 1)):
            if len(out) >= n:
                break
            out.add(elements.example(rng))
        return out
    return _Strategy(draw)


def dictionaries(keys: _Strategy, values: _Strategy, *, min_size: int = 0,
                 max_size: int = 10, **_kw) -> _Strategy:
    def draw(rng):
        n = rng.randint(min_size, max_size)
        out = {}
        for _ in range(8 * max(n, 1)):
            if len(out) >= n:
                break
            out[keys.example(rng)] = values.example(rng)
        return out
    return _Strategy(draw)


class _DataObject:
    """Interactive draws inside a test body (``st.data()``)."""

    def __init__(self, rng: random.Random):
        self._rng = rng

    def draw(self, strategy: _Strategy, label: str | None = None):
        return strategy.example(self._rng)


def data() -> _Strategy:
    return _Strategy(lambda rng: _DataObject(rng))


def given(*arg_strats, **kw_strats):
    def deco(fn):
        @functools.wraps(fn)
        def run(*args, **kwargs):
            n = min(getattr(run, "_hyp_max_examples", MAX_EXAMPLES),
                    MAX_EXAMPLES)
            for ex in getattr(run, "_hyp_examples", ()):
                fn(*args, **kwargs, **ex)
            rng = random.Random(0xF1A2E)
            for _ in range(n):
                vals = [s.example(rng) for s in arg_strats]
                kvals = {k: s.example(rng) for k, s in kw_strats.items()}
                fn(*args, *vals, **kwargs, **kvals)
        # pytest plugins (anyio et al.) probe fn.hypothesis.inner_test
        run.hypothesis = types.SimpleNamespace(inner_test=fn)
        # hide the strategy-filled params from pytest's fixture resolver
        del run.__wrapped__
        run.__signature__ = inspect.Signature()
        return run
    return deco


def settings(max_examples: int = MAX_EXAMPLES, deadline=None, **_kw):
    def deco(fn):
        fn._hyp_max_examples = max_examples
        return fn
    return deco


def example(**kw):
    """Run ``kw`` as an explicit example before the drawn ones."""
    def deco(fn):
        fn._hyp_examples = [kw, *getattr(fn, "_hyp_examples", ())]
        return fn
    return deco


def assume(condition) -> bool:
    # real hypothesis aborts the example; the stub's draws are benign
    # enough that skipping the abort machinery is fine for a smoke run
    return bool(condition)


class HealthCheck:
    too_slow = "too_slow"
    data_too_large = "data_too_large"
    all = classmethod(lambda cls: [])


def install() -> None:
    """Register this module as ``hypothesis`` (+ ``.strategies``)."""
    if "hypothesis" in sys.modules:
        return
    mod = types.ModuleType("hypothesis")
    strategies = types.ModuleType("hypothesis.strategies")
    for name in ("integers", "floats", "booleans", "sampled_from",
                 "tuples", "lists", "sets", "dictionaries", "data"):
        setattr(strategies, name, globals()[name])
    mod.given = given
    mod.settings = settings
    mod.example = example
    mod.assume = assume
    mod.HealthCheck = HealthCheck
    mod.strategies = strategies
    mod.__version__ = "0.0.0-offline-stub"
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = strategies

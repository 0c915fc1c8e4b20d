import os
import sys

# smoke tests and benches must see ONE device (the dry-run sets its own
# XLA_FLAGS in-process; never globally here).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))


def _install_hypothesis_shim() -> None:
    """Make ``from hypothesis import example, given, settings, strategies``
    work in containers without hypothesis installed.

    The shim is a deliberately tiny stand-in: ``@given`` draws a fixed number
    of pseudo-random examples from the strategies (deterministic seed, no
    shrinking, no edge-case bias) — enough to keep the property tests
    meaningful and the suite collectible.  When the real hypothesis is
    importable it is always preferred.
    """
    try:
        import hypothesis  # noqa: F401
        return
    except ImportError:
        pass

    import types

    import numpy as _np

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    def floats(min_value=0.0, max_value=1.0, **_ignored):
        return _Strategy(
            lambda r: float(min_value + (max_value - min_value) * r.random()))

    def integers(min_value=0, max_value=100):
        return _Strategy(lambda r: int(r.integers(min_value, max_value + 1)))

    def sampled_from(elements):
        elements = list(elements)
        return _Strategy(lambda r: elements[int(r.integers(len(elements)))])

    def lists(elements, min_size=0, max_size=10):
        def draw(r):
            size = int(r.integers(min_size, max_size + 1))
            return [elements.draw(r) for _ in range(size)]
        return _Strategy(draw)

    def just(value):
        return _Strategy(lambda r: value)

    def tuples(*strategies):
        return _Strategy(lambda r: tuple(s.draw(r) for s in strategies))

    def one_of(*strategies):
        strategies = [s for group in strategies
                      for s in (group if isinstance(group, (list, tuple))
                                else (group,))]
        return _Strategy(
            lambda r: strategies[int(r.integers(len(strategies)))].draw(r))

    def given(*strategies):
        def deco(fn):
            # zero-arg wrapper (not functools.wraps): the strategy parameters
            # must not leak into the signature pytest inspects for fixtures
            def wrapper():
                for kwargs in getattr(fn, "_shim_examples", ()):
                    fn(**kwargs)
                rng = _np.random.default_rng(0)
                for _ in range(getattr(fn, "_shim_max_examples", 20)):
                    fn(*[s.draw(rng) for s in strategies])
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            wrapper.__module__ = fn.__module__
            wrapper.__dict__.update(fn.__dict__)   # keep pytest marks
            return wrapper
        return deco

    def settings(max_examples=20, deadline=None, **_ignored):
        def deco(fn):
            fn._shim_max_examples = max_examples
            return fn
        return deco

    def example(**kwargs):
        """Explicit example; apply it below ``@given`` so the wrapper sees
        it."""
        def deco(fn):
            fn._shim_examples = [kwargs, *getattr(fn, "_shim_examples", ())]
            return fn
        return deco

    mod = types.ModuleType("hypothesis")
    mod.__doc__ = "pytest-time fallback shim (see tests/conftest.py)"
    st_mod = types.ModuleType("hypothesis.strategies")
    for f in (floats, integers, sampled_from, lists, just, tuples, one_of):
        setattr(st_mod, f.__name__, f)
    mod.given = given
    mod.example = example
    mod.settings = settings
    mod.strategies = st_mod
    sys.modules["hypothesis"] = mod
    sys.modules["hypothesis.strategies"] = st_mod


_install_hypothesis_shim()

import jax
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng_key():
    return jax.random.key(0)

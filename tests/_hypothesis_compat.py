"""Minimal stand-in for ``hypothesis`` so the property tests still run (with
a deterministic sampler) when the optional dep is missing.

When hypothesis IS installed (see requirements-dev.txt) it is re-exported
unchanged. Otherwise ``given`` expands each strategy into a fixed number of
seeded pseudo-random examples — weaker shrinking/coverage than the real
thing, but the invariants get exercised either way and collection never
fails on the import.
"""
from __future__ import annotations

try:  # pragma: no cover - exercised only when hypothesis is installed
    from hypothesis import example, given, settings, strategies  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:
    import inspect
    import random

    HAVE_HYPOTHESIS = False

    class _Strategy:
        def __init__(self, draw):
            self._draw = draw

        def example(self, rng):
            return self._draw(rng)

    class strategies:  # noqa: N801 - mirrors the hypothesis module name
        @staticmethod
        def integers(min_value, max_value):
            return _Strategy(lambda rng: rng.randint(min_value, max_value))

        @staticmethod
        def floats(min_value, max_value):
            return _Strategy(lambda rng: rng.uniform(min_value, max_value))

        @staticmethod
        def sampled_from(options):
            options = list(options)
            return _Strategy(lambda rng: rng.choice(options))

        @staticmethod
        def booleans():
            return _Strategy(lambda rng: rng.random() < 0.5)

    _DEFAULT_MAX_EXAMPLES = 10

    def example(**_pinned):
        """Pinned examples are a hypothesis feature; the fallback sampler
        draws its own."""
        return lambda fn: fn

    def settings(max_examples=_DEFAULT_MAX_EXAMPLES, **_ignored):
        """Accepts (and mostly ignores) the hypothesis knobs; only
        max_examples matters to the fallback sampler."""

        def deco(fn):
            fn._compat_max_examples = max_examples
            return fn

        return deco

    def given(**strategy_kwargs):
        def deco(fn):
            def wrapper(*args, **kwargs):
                n = getattr(wrapper, "_compat_max_examples", None) or getattr(
                    fn, "_compat_max_examples", _DEFAULT_MAX_EXAMPLES
                )
                # deterministic per-test stream so failures reproduce
                rng = random.Random(fn.__name__)
                for i in range(n):
                    drawn = {
                        k: s.example(rng) for k, s in strategy_kwargs.items()
                    }
                    try:
                        fn(*args, **drawn, **kwargs)
                    except Exception as e:  # noqa: BLE001 - re-raise w/ context
                        raise AssertionError(
                            f"falsifying example ({i + 1}/{n}): {drawn!r}"
                        ) from e

            # hide the drawn parameters from pytest's fixture resolution
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            wrapper._compat_max_examples = getattr(fn, "_compat_max_examples", None)
            sig = inspect.signature(fn)
            wrapper.__signature__ = sig.replace(
                parameters=[
                    p for name, p in sig.parameters.items()
                    if name not in strategy_kwargs
                ]
            )
            return wrapper

        return deco

"""Environment compatibility for the test suite.

``hypothesis`` may be absent, so it is gated here instead of imported
directly.  A deterministic random-sampling fallback implements the small
slice of the API the suite uses (``given`` with keyword strategies,
``settings(max_examples=..., deadline=...)``,
``st.integers/floats/sampled_from/booleans``).  Property tests then run
``max_examples`` seeded random draws — weaker than hypothesis shrinking, but
the invariants still execute.
"""
from __future__ import annotations

import functools
import zlib

import numpy as np

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True

    # Pinned profile so real-hypothesis runs are as deterministic as the
    # fallback sampler below (which derives its seed from the test name):
    # derandomize fixes the example stream per test, deadline is off
    # because CPU-jax jit compiles inside examples blow any wall-clock
    # budget on first execution.
    settings.register_profile("repro", derandomize=True, deadline=None,
                              print_blob=False)
    settings.load_profile("repro")
except ImportError:                                # pragma: no cover
    HAVE_HYPOTHESIS = False

    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    class _Strategies:
        @staticmethod
        def integers(min_value, max_value):
            return _Strategy(
                lambda rng: int(rng.integers(min_value, max_value + 1)))

        @staticmethod
        def floats(min_value, max_value):
            return _Strategy(
                lambda rng: float(rng.uniform(min_value, max_value)))

        @staticmethod
        def sampled_from(elements):
            seq = list(elements)
            return _Strategy(lambda rng: seq[int(rng.integers(len(seq)))])

        @staticmethod
        def booleans():
            return _Strategy(lambda rng: bool(rng.integers(2)))

    st = _Strategies()

    def settings(max_examples: int = 20, **_ignored):
        def deco(fn):
            fn._compat_max_examples = max_examples
            return fn
        return deco

    def given(**strategies):
        def deco(fn):
            import inspect

            def run(*args, **kwargs):
                n = getattr(run, "_compat_max_examples",
                            getattr(fn, "_compat_max_examples", 20))
                seed = zlib.crc32(fn.__qualname__.encode())
                rng = np.random.default_rng(seed)
                for _ in range(n):
                    drawn = {k: s.draw(rng) for k, s in strategies.items()}
                    fn(*args, **kwargs, **drawn)

            # expose a signature WITHOUT the drawn params so pytest doesn't
            # treat them as fixtures (functools.wraps would leak them)
            run.__name__, run.__doc__ = fn.__name__, fn.__doc__
            run.__module__, run.__qualname__ = fn.__module__, fn.__qualname__
            sig = inspect.signature(fn)
            keep = [p for name, p in sig.parameters.items()
                    if name not in strategies]
            run.__signature__ = sig.replace(parameters=keep)
            return run
        return deco


"""Hypothesis-optional shim: property tests need the dev extra
(`pip install .[dev]`); unit tests in the same modules still run from a
clean checkout without hypothesis — the `@given` tests skip instead.

The skip fallback is for OFFLINE checkouts only. CI pins hypothesis in the
[dev] extra and exports REQUIRE_HYPOTHESIS=1 after a successful install
(scripts/ci.sh): with that set, a missing hypothesis turns every `@given`
test into a loud failure instead of a silent skip, so the property-based
differential suite can never be masked out of a CI run by a broken dep.
`HAVE_HYPOTHESIS` lets test modules branch (e.g. deterministic fixed-seed
examples always run; the generative budget only applies when real).
"""

import os

import pytest

try:
    from hypothesis import example, given, settings, strategies as st  # noqa: F401

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

    class _LazyStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _LazyStrategies()

    def settings(**kw):
        return lambda f: f

    def example(*a, **kw):
        return lambda f: f

    def given(*a, **kw):
        if os.environ.get("REQUIRE_HYPOTHESIS"):

            def deco(f):
                # plain *args wrapper (no functools.wraps): copying the
                # signature would make pytest resolve the @given parameters
                # as fixtures
                def loud_failure(*args, **kwargs):
                    pytest.fail(
                        "REQUIRE_HYPOTHESIS=1 but hypothesis is not "
                        "installed: @given property tests would silently "
                        "skip (pip install -e '.[dev]')"
                    )

                loud_failure.__name__ = f.__name__
                loud_failure.__doc__ = f.__doc__
                return loud_failure

            return deco
        return lambda f: pytest.mark.skip(reason="hypothesis not installed")(f)

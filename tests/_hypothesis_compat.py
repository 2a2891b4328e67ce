"""Import hypothesis, or stub it so test modules still collect.

The tier-1 image does not ship ``hypothesis`` (it is a test extra in
pyproject.toml).  Modules using property tests import ``given`` /
``settings`` / ``st`` from here: with hypothesis installed they are the
real thing; without it the property tests are collected as skips and
the deterministic tests keep running.
"""

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ModuleNotFoundError:
    import pytest

    HAVE_HYPOTHESIS = False

    def given(*_a, **_k):
        return pytest.mark.skip(reason="hypothesis is not installed")

    def settings(*_a, **_k):
        return lambda fn: fn

    example = settings

    class _StrategiesStub:
        @staticmethod
        def composite(_fn):
            return lambda *a, **k: None

        def __getattr__(self, _name):
            return lambda *a, **k: None

    st = _StrategiesStub()

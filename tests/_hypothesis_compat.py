"""Re-export of Hypothesis's ``given``/``settings``/``st`` for the
test-suite, which imports them from here."""

from hypothesis import given, settings, strategies as st  # noqa: F401

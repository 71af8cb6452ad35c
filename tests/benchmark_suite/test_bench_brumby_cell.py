"""Tier-1 (``pytest tests/``) collects the benchmark's own tests from
here; they live, once, in ``benchmarks/tests/test_brumby_cell.py``."""

from benchmarks.tests.test_brumby_cell import *  # noqa: F401,F403

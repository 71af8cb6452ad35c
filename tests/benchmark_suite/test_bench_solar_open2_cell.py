"""Tier-1 (``pytest tests/``) collects the benchmark's own tests from
here; they live, once, in ``benchmarks/tests/test_solar_open2_cell.py``."""

from benchmarks.tests.test_solar_open2_cell import *  # noqa: F401,F403

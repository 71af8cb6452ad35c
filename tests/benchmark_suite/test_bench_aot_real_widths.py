"""Tier-1 (``pytest tests/``) collects the benchmark's own tests from
here; they live, once, in ``benchmarks/tests/test_aot_real_widths.py``."""

from benchmarks.tests.test_aot_real_widths import *  # noqa: F401,F403

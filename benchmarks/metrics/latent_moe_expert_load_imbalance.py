"""Median over the window's decode chunks of the busiest held expert's rows /
the mean rows per held expert over the blocks that HAVE experts (the 'E'
blocks of ``hybrid_override_pattern``, not every layer): 1 = even.
"""

from benchmarks.lib import nemotron_names

read = nemotron_names.load_imbalance

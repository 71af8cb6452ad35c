"""Median time per output token after the first, per request.
"""

from benchmarks.lib import readers

read = readers.tpot_percentile(50)

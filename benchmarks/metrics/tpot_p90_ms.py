"""90th percentile over requests of the time per output token after the
first: in effect the decode chunk's length over the shortest answers.
"""

from benchmarks.lib import readers

read = readers.tpot_percentile(90)

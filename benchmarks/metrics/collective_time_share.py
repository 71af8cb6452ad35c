"""Union of collective ops on chip 0 / device time of the steps.
"""

from benchmarks.lib import readers

read = readers.collective_time_share

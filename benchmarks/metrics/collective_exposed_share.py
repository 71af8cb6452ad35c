"""The part of the collectives' time with no other op running on chip 0 /
device time of the steps.
"""

from benchmarks.lib import readers

read = readers.collective_exposed_share

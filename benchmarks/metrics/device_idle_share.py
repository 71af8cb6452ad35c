"""1 - busy / traced span, mean over the cell's chips.
"""

from benchmarks.lib import readers

read = readers.device_idle_share

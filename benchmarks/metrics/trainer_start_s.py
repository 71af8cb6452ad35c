"""fit() called -> first step launched, with the compile (or cache fetch)
seconds before it taken out.
"""

from benchmarks.lib import readers

read = readers.trainer_start_s

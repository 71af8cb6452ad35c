"""Own device time of the ops whose path holds ``rematted_computation`` (what
the remat policy computes a second time), any scope / device time of the steps.
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("train", None, "remat")

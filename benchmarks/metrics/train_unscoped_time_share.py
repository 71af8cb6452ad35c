"""Own device time of the ops no scope of the program's vocabulary reaches /
device time of the steps.
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("train", "unscoped")

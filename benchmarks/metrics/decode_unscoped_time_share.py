"""Own device time of the ops no scope of the program's vocabulary reaches /
device time of the decode programs.
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("decode", "unscoped")

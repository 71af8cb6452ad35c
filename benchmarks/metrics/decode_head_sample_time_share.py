"""Own device time of the ops under scopes ``head`` and ``sample`` / device time
of the decode programs.
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("decode", "head_sample")

"""Own device time of the ops under scope ``index_select`` (the exact top-k of
a row's index scores: 32 counting passes over them, a mask) / device time of
the decode programs.
"""

from benchmarks.lib import dsa_names

read = dsa_names.scope_time_share("index_select")

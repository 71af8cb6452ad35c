"""Own device time of the ops under scope ``shared_expert`` (the full-width
two-matrix expert every token passes) / device time of the decode programs.
"""

from benchmarks.lib import nemotron_names

read = nemotron_names.shared_expert_time_share

"""Device time of the recurrent-state update's ops (told by their arrays,
``[slots, heads, head_dim, state]``) / device time of the decode programs.
"""

from benchmarks.lib import ssm_names

read = ssm_names.state_update_time_share

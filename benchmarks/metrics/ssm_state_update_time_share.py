"""Device time of the recurrent-state update's ops (told by the kernel's name
or their arrays, ``[slots, heads, head_dim, state]``; by the scope
``ssm_state_update`` where the configuration's file says so) / device time of
the decode programs.
"""

from benchmarks.lib import ssm_names

read = ssm_names.state_update_time_share

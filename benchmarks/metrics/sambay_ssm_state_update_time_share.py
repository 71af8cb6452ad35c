"""Device time of the Mamba-1 state update (scope ``mamba1_state_update``) /
device time of the decode programs.
"""

from benchmarks.lib import sambay_names

read = sambay_names.ssm_state_update_time_share

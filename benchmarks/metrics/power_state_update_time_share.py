"""Device time of a decode step's power-retention state update (scope
``power_state_update``) / device time of the decode programs.
"""

from benchmarks.lib import power_names

read = power_names.state_update_time_share

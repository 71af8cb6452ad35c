"""Device time of a decode step's delta-rule state update (scope
``kda_state_update``) / device time of the decode programs.
"""

from benchmarks.lib import kda_names

read = kda_names.state_update_time_share

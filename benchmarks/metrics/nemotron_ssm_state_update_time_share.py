"""Own device time of the ops under scope ``ssm_state_update`` (the grouped
Mamba-2 update of every advancing slot's recurrent state) / device time of
the decode programs.
"""

from benchmarks.lib import nemotron_names

read = nemotron_names.state_update_time_share

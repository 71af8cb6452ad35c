"""Own device time of the ops under scopes router and expert_dispatch, every
phase / device time of the train steps.
"""

from benchmarks.lib import moe_names

read = moe_names.train_routing_time_share

"""Own device time of the ops under scope ``shared_expert`` (the expert every
token passes beside its routed experts) / device time of the decode
programs.
"""

from benchmarks.lib import moe_names

read = moe_names.shared_expert_time_share

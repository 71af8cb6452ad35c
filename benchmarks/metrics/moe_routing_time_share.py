"""Own device time of the ops the program traced under ``router`` and
``expert_dispatch`` (scores, top-k, the sort by expert, the grouped matmuls'
metadata, gather, un-sort and gate-weighted sum) / device time of the decode
programs.
"""

from benchmarks.lib import moe_names

read = moe_names.routing_time_share

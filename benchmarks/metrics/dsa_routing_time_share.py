"""Own device time of the ops under scopes ``router`` and ``expert_dispatch``
(the softmax over 128 experts, the top-8, the sort by expert, the gather of
rows, the un-sort and the gate-weighted sum) / device time of the decode
programs.
"""

from benchmarks.lib import dsa_names

read = dsa_names.scope_time_share(*dsa_names.ROUTING_SCOPES)

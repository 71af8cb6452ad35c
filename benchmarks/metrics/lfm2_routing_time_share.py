"""Own device time of the ops under scopes ``router`` and ``expert_dispatch``
(sigmoid scores, the biased top-k, the sort by expert, the gather of rows,
the un-sort and the gate-weighted sum) / device time of the decode programs.
"""

from benchmarks.lib import lfm2_names

read = lfm2_names.scope_time_share(*lfm2_names.ROUTING_SCOPES)

"""Own device time of the ops under scope ``expert_dispatch`` (the sort by
expert, counts, gather, un-sort and gate-weighted sum) / device time of the
prefill programs.
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("prefill", "expert_dispatch")

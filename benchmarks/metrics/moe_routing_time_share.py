"""Device time of an expert layer's routing (router, top-k, sort, counts,
gather, un-sort, combine) / device time of the decode programs.
"""

from benchmarks.lib import moe_names

read = moe_names.time_share("routing")

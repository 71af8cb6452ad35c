"""Own device time of the ops under scope ``indexer`` (the three index
projections of a step's token and its index queries against every index key
its row holds) / device time of the decode programs.
"""

from benchmarks.lib import dsa_names

read = dsa_names.scope_time_share("indexer")

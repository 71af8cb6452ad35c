"""Own device time of the ops under scopes ``indexer`` and ``index_select``
(a prompt's index projections, its index scores a query tile at a time and
the bisection that makes the mask) / device time of the prefill programs.
"""

from benchmarks.lib import dsa_names

read = dsa_names.scope_time_share(*dsa_names.SELECTION_SCOPES,
                                  which="prefill")

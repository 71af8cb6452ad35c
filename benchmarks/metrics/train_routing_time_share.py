"""Own device time of the ops under scopes router and expert_dispatch, every
phase / device time of the train steps.  Not entered in BENCHMARK.json yet
(PERF.md section 7).
"""

from benchmarks.lib import afmoe_names

read = afmoe_names.routing_time_share

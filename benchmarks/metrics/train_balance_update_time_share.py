"""Own device time of the ops under scope router_balance (the selection bias's
update after the optimizer) / device time of the train steps.  Not entered in
BENCHMARK.json yet (PERF.md section 7).
"""

from benchmarks.lib import afmoe_names

read = afmoe_names.balance_update_time_share

"""Own device time of the ops under scope expert_ffn, every phase / device time
of the train steps.  Not entered in BENCHMARK.json yet (PERF.md section 7).
"""

from benchmarks.lib import afmoe_names

read = afmoe_names.expert_ffn_time_share

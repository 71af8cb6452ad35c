"""serve.wait_slot, 90th percentile: seen by the scheduler -> bound to a slot.
"""

from benchmarks.lib import program_spans

read = program_spans.phase_percentile("serve.wait_slot", 90)

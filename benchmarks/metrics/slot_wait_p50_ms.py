"""serve.wait_slot, median: seen by the scheduler -> bound to a slot.
"""

from benchmarks.lib import program_spans

read = program_spans.phase_percentile("serve.wait_slot", 50)

"""serve.wait_prefill, median: bound to a slot -> first token harvested (the
chunk in flight, then the prefill program, then the next harvest).
"""

from benchmarks.lib import program_spans

read = program_spans.phase_percentile("serve.wait_prefill", 50)

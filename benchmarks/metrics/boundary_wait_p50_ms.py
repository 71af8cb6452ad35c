"""serve.wait_boundary, median: engine submit -> the scheduler thread takes
the request off the queue, which it does between chunks.
"""

from benchmarks.lib import program_spans

read = program_spans.phase_percentile("serve.wait_boundary", 50)

"""Gap between the bursts in which a request's tokens reach the host (the
harvest stamps of serve.request), 90th percentile over all gaps.
"""

from benchmarks.lib import program_spans

read = program_spans.token_burst_gap_percentile(90)

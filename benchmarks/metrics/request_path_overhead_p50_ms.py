"""Per request, joined on the trace id: (handle span start -> engine submit) +
(engine done -> serve.response settled); the median.
"""

from benchmarks.lib import program_spans

read = program_spans.request_path_overhead_p50_ms

"""serve.prefill_group: 1 - prompt tokens / (padded rows x length bucket), over
the groups launched in the window.
"""

from benchmarks.lib import program_spans

read = program_spans.prefill_padding_share

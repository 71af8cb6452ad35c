"""serve.chunk: tokens kept (appended to a live request) / token-steps
computed (chunk length x max_slots), over the chunks launched in the window.
"""

from benchmarks.lib import program_spans

read = program_spans.decode_slot_utilization

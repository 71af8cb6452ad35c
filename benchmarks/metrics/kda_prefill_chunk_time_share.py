"""Device time of the delta rule's chunked form (scope ``kda_chunk``: the
decayed products, the triangular solve and the scan over the chunks, XLA's:
no kernel of its own) / device time of the prefill programs.
"""

from benchmarks.lib import kda_names

read = kda_names.prefill_chunk_time_share

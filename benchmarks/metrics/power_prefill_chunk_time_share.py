"""Device time of power retention's chunked form (scope ``power_chunk``:
the quadratic form inside a chunk, ``phi`` of a chunk, the state's read and
update across chunks) / device time of the prefill programs.
"""

from benchmarks.lib import power_names

read = power_names.prefill_chunk_time_share

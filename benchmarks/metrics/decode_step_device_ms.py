"""Median device time of the decode program / decode_chunk.
"""

from benchmarks.lib import readers

read = readers.decode_step_device_ms

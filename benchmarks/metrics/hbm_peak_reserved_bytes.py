"""memory_stats() peak_bytes_reserved (XLA's scratch) at the window's close,
fullest chip.
"""

from benchmarks.lib import readers

read = readers.hbm_peak_reserved_bytes

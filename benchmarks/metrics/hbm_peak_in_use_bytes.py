"""memory_stats() peak_bytes_in_use at the window's close, fullest chip.
"""

from benchmarks.lib import readers

read = readers.hbm_peak_in_use_bytes

"""Device time of the Mosaic flash kernels (fwd, dq, dk/dv) / device time
of the steps.
"""

from benchmarks.lib import readers

read = readers.flash_attention_time_share

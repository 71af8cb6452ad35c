"""Device time of the forward flash kernel (by its name) / device time of the
steps.
"""

from benchmarks.lib import flash_names

read = flash_names.time_share("fwd")

"""Device time of the gated memory units (scope ``gmu``: two matmuls and the
gate by layer 16's scan output) / device time of the decode programs.
"""

from benchmarks.lib import sambay_names

read = sambay_names.gmu_time_share

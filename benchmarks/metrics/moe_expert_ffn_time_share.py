"""Device time of the grouped-matmul kernels (by their names) / device
time of the decode programs.
"""

from benchmarks.lib import moe_names

read = moe_names.time_share("matmul")

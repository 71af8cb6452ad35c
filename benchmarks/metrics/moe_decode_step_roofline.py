"""Least time of a decode step of a model with experts (attention, router
and head weights once, the three matrices of each expert TOUCHED, the
batch's K/V once: HBM bytes or FLOPs at peak) / its measured time.
"""

from benchmarks.lib import moe_names

read = moe_names.decode_step_roofline

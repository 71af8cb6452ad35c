"""Least time of a decode step of a model with state-space layers (every
matmul weight once, the recurrent and conv states of the slots the step
advances read and written once, the batch's K/V once: HBM bytes or FLOPs
at peak) / its measured time.
"""

from benchmarks.lib import ssm_names

read = ssm_names.decode_step_roofline

"""Least time of a decode step's grouped matmuls alone (the touched
experts' matrices and the rows' activations: HBM bytes or FLOPs at peak)
/ their measured time: the grouped-matmul kernel's share of its roofline.
"""

from benchmarks.lib import moe_names

read = moe_names.expert_matmul_roofline

"""Least time of a decode step's grouped matmuls alone (the touched experts'
matrices at an expert's own width and the rows' activations: HBM bytes or
FLOPs at peak) / the ``%ragged-dot-none*`` kernels' measured time a step.
"""

from benchmarks.lib import lfm2_names

read = lfm2_names.expert_matmul_roofline

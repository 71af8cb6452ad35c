"""Least time of a decode step's grouped matmuls alone (the touched experts'
matrices at an expert's own width, 768, and the rows' activations: HBM bytes
or FLOPs at peak) / the ``%ragged-dot-none*`` kernels' measured time a step:
what one row a group makes of the bandwidth.
"""

from benchmarks.lib import dsa_names

read = dsa_names.expert_matmul_roofline

"""Least time of a step's grouped matmuls over the routed experts in their
latent (each touched expert's TWO matrices of 1,024 x 2,688 and its rows'
activations, at peak: ``lib/nemotron_flops.py``) / the measured time of the
``%ragged-dot-none*`` kernels a step.
"""

from benchmarks.lib import nemotron_names

read = nemotron_names.expert_matmul_roofline

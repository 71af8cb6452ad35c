"""Least time of a decode step of a model with an indexer (every non-expert
matmul weight once, three matrices of each (layer, expert) touched, every
index key a live row holds once, K and V of the ``min(length, topk)`` rows
it attends: HBM bytes or the step's FLOPs at peak, the larger) / its
measured time: the share of the WHOLE step.
"""

from benchmarks.lib import dsa_names

read = dsa_names.decode_step_roofline

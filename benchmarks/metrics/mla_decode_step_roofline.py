"""Least time of a decode step of a latent-attention model that holds a share
of its experts (dense, shared and head weights once, three matrices of each
(layer, held expert) touched, the latent rows in flight: HBM bytes or the
step's FLOPs at peak, the larger) / its measured time.
"""

from benchmarks.lib import mla_names

read = mla_names.decode_step_roofline

"""Least time of a decode step of a short-convolution + expert model (every
non-expert matmul weight once, three matrices of each (layer, expert)
touched, the K/V in flight as far as each row is long, the conv states of
the slots advanced read and written once: HBM bytes or the step's FLOPs at
peak, the larger) / its measured time.
"""

from benchmarks.lib import lfm2_names

read = lfm2_names.decode_step_roofline

"""Least time of a decode step of a model with window layers (dense weights
once, the experts the step touched once, each live row's keys once -- a
ring layer's at ``min(length, window)``: HBM bytes or FLOPs at peak) / its
measured time.
"""

from benchmarks.lib import swa_names

read = swa_names.decode_step_roofline

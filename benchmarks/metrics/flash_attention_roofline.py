"""Least time for the kernels' causal FLOPs / bytes at the chip's peaks /
their measured time.
"""

from benchmarks.lib import readers

read = readers.flash_attention_roofline

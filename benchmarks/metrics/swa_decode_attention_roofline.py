"""Least time of a step's attention (every key a live row attends, K and V
once, a ring layer's at ``min(length, window)``, at peak) / the measured
time of the ``decode_attention`` kernel a step.
"""

from benchmarks.lib import swa_names

read = swa_names.decode_attention_roofline

"""Least time for the three flash kernels' FLOPs / bytes of a step at the
chip's peaks (inside the layers' masks, by the counts of the module the
configuration's file names: ``train_counts``) / their measured time.
"""

from benchmarks.lib import readers

read = readers.flash_attention_roofline

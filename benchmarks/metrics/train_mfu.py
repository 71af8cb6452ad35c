"""tokens/s per chip x train_flops_per_token / the chip's bf16 peak (no
recompute, no embedding lookup): the whole step's share of the peak, by the
counts of the module the configuration's file names (``train_counts``;
``lib/flops.py`` where it names none).
"""

from benchmarks.lib import readers

read = readers.train_mfu

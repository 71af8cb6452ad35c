"""tokens/s per chip x flops.train_flops_per_token / the chip's bf16 peak
(no recompute, no embedding lookup).
"""

from benchmarks.lib import readers

read = readers.train_mfu

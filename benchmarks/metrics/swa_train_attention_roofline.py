"""Least time of the flash kernels' work inside the masks (4 banded + 1 causal
layer's pairs x 3.5: FLOPs or bytes at the chip's peaks) / the forward + dq +
dk/dv kernels' measured time a step.  Not entered in BENCHMARK.json yet
(PERF.md section 7).
"""

from benchmarks.lib import afmoe_names

read = afmoe_names.swa_train_attention_roofline

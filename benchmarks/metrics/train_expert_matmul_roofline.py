"""Least time of a step's grouped matmuls forward + backward over the rows the
held experts computed (the step's own expert_rows metric; FLOPs or bytes at
peak, no recompute) / the %ragged-dot-none* kernels' measured time a step.
"""

from benchmarks.lib import moe_names

read = moe_names.train_expert_matmul_roofline

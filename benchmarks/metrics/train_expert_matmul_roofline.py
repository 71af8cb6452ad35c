"""Least time of a step's grouped matmuls forward + backward over the rows the
held experts computed (FLOPs or bytes at peak, no recompute) / the
%ragged-dot-none* kernels' measured time a step; nothing until the kind hands
the step's expert_rows over.  Not entered in BENCHMARK.json yet (PERF.md
section 7).
"""

from benchmarks.lib import afmoe_names

read = afmoe_names.train_expert_matmul_roofline

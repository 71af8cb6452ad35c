"""Least time of power retention's chunked form over the positions the
traced prefill programs sent through it (``power_flops.chunk_flops`` /
``chunk_bytes`` at peak, the larger) / the measured time of the ops under
``power_chunk``.
"""

from benchmarks.lib import power_names

read = power_names.prefill_chunk_roofline

"""Least time of a decode step at the batch in flight (HBM bytes or FLOPs
at peak) / its measured time.
"""

from benchmarks.lib import readers

read = readers.decode_step_roofline

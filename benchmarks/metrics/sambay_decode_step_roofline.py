"""Least time of a decode step of a decoder-hybrid-decoder (every weight once,
the ONE full-length K/V pool's live rows once a reading layer -- eight of
them --, the rings' live rows, the Mamba-1 and conv states of the rows it
advances read and written: HBM bytes or FLOPs at peak, the larger) / its
measured time.
"""

from benchmarks.lib import sambay_names

read = sambay_names.decode_step_roofline

"""Least time of a step's reads of the window layers' rings (each live row's
last 512 keys and values once a window layer at the HBM peak) / the measured
time of the ops under ``decode_attention`` and ``attention``.
"""

from benchmarks.lib import sambay_names

read = sambay_names.window_attention_roofline

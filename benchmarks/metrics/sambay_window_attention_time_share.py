"""Device time of the window layers' decode attention (the ``decode_attention``
kernel over their rings, and what else is traced under ``attention``) /
device time of the decode programs.
"""

from benchmarks.lib import sambay_names

read = sambay_names.window_attention_time_share

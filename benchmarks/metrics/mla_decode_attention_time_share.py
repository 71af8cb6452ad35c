"""Device time of the ``mla_decode_attention`` kernel (by its event name) /
device time of the decode programs.
"""

from benchmarks.lib import mla_names

read = mla_names.decode_attention_time_share

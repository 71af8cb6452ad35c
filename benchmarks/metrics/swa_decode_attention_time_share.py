"""Device time of the ``decode_attention`` kernel's calls (full pool and
rings, 4 kv heads) / device time of the decode programs.
"""

from benchmarks.lib import swa_names

read = swa_names.decode_attention_time_share

"""Device time of the expanded path's ``flash_prefill_attention`` calls (by
their event name) / device time of the prefill programs.
"""

from benchmarks.lib import mla_names

read = mla_names.prefill_attention_time_share

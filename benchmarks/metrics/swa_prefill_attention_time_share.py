"""Device time of the ``flash_prefill_attention`` kernel's calls / device
time of the prefill programs.
"""

from benchmarks.lib import swa_names

read = swa_names.prefill_attention_time_share

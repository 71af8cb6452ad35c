"""Device time of the ops traced under ``cross_attention`` (a step's eight
reads of the one full-length K/V pool: the K/V layer's own and the seven
cross layers') / device time of the decode programs.
"""

from benchmarks.lib import sambay_names

read = sambay_names.shared_kv_attention_time_share

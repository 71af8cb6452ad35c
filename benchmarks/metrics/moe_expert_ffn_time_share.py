"""Own device time of the ops the program traced under ``expert_ffn`` (the
grouped matmuls and the activation between them) / device time of the decode
programs.
"""

from benchmarks.lib import moe_names

read = moe_names.expert_ffn_time_share

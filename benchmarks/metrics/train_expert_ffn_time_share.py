"""Own device time of the ops under scope expert_ffn (the grouped matmuls and
the activation between them), every phase / device time of the train steps.
"""

from benchmarks.lib import moe_names

read = moe_names.train_expert_ffn_time_share

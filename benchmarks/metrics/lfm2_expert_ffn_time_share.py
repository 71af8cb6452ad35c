"""Own device time of the ops under scope ``expert_ffn`` (the grouped
matmuls and the activation between them) / device time of the decode
programs.
"""

from benchmarks.lib import lfm2_names

read = lfm2_names.scope_time_share("expert_ffn")

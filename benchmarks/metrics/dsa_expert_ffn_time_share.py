"""Own device time of the ops under scope ``expert_ffn`` (the grouped matmuls
over 128 experts of 768 and the activation between them) / device time of
the decode programs: what sets the step beside the selection.
"""

from benchmarks.lib import dsa_names

read = dsa_names.scope_time_share("expert_ffn")

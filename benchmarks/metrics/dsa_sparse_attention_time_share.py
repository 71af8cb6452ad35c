"""Own device time of the ops under scope ``sparse_attention`` (the selection
laid out block by block for the kernel) and of the kernel that attends
through it, under its own scope ``decode_attention`` / device time of the
decode programs.
"""

from benchmarks.lib import dsa_names

read = dsa_names.scope_time_share(*dsa_names.ATTENTION_SCOPES)

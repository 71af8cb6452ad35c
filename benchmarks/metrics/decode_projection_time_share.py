"""Own device time of the ops under the projection scopes (``qkv_proj``,
``attn_out``, ``ssm_proj``, ``ssm_out``) / device time of the decode programs.
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("decode", "projection")

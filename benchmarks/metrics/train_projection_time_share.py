"""Own device time of the ops under the projection scopes (``qkv_proj``,
``attn_out``), every phase / device time of the steps.
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("train", "projection")

"""Own device time of the ops under the FFN scopes (``ffn``, and an expert
layer's ``router``, ``expert_dispatch``, ``expert_ffn``), every phase / device
time of the steps.
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("train", "ffn")

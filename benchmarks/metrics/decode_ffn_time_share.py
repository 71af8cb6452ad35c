"""Own device time of the ops under the FFN scopes (``ffn``, ``router``,
``expert_dispatch``, ``expert_ffn``) / device time of the decode programs.
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("decode", "ffn")

"""Own device time of the ops under scopes ``decode_attention`` and
``attention`` (the ONE attention block's read of its pool: 2 K/V heads stored
as rows, sixteen queries a head) / device time of the decode programs.
"""

from benchmarks.lib import nemotron_names

read = nemotron_names.decode_attention_time_share

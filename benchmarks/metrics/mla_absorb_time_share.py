"""Own device time of the ops under scope ``mla_absorb`` (the W_UK and W_UV
matmuls of the absorbed decode) / device time of the decode programs.
"""

from benchmarks.lib import mla_names

read = mla_names.scope_time_share("mla_absorb")

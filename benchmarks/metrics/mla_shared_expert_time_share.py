"""Own device time of the ops under scope ``shared_expert`` (the SwiGLU every
token passes beside its routed experts) / device time of the decode
programs.
"""

from benchmarks.lib import mla_names

read = mla_names.scope_time_share("shared_expert")

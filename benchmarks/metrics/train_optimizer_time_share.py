"""Own device time of the ops under scope ``optimizer`` / device time of the
steps (the scope map: ``lib/scope_names.py``).
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("train", "optimizer")

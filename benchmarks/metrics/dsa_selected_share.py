"""serve.chunk: keys the live rows attend (``min(length, topk)`` a row) / keys
they hold, over the window's chunks: how sparse this TRAFFIC makes the
attention.  The scheduler's arithmetic over the rows' lengths, not a count
of what the device selected: it reads the same whatever the program attends
(``correct`` and ``tools/dsa_check.py`` hold the selection).
"""

from benchmarks.lib import dsa_names

read = dsa_names.selected_share

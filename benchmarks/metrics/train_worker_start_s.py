"""train.worker_start: fit() entered -> the user's loop entered, the mesh,
the state's initialisation and its placement inside.
"""

from benchmarks.lib import start_spans

read = start_spans.reader("span_s", "train.worker_start")

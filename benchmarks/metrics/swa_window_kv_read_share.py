"""serve.chunk: keys a ring layer reads for the live rows / keys a full
layer reads for the same rows, over the window's chunks, in %.
"""

from benchmarks.lib import swa_names

read = swa_names.window_kv_read_share

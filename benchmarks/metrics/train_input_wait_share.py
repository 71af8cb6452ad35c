"""Host seconds inside next(batches) / window.
"""

from benchmarks.lib import readers

read = readers.train_input_wait_share

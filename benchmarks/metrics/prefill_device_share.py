"""Device time of the prefill programs / busy time.
"""

from benchmarks.lib import readers

read = readers.prefill_device_share

"""Median device time of one execution of the step program (XLA Modules
line, chip 0).
"""

from benchmarks.lib import readers

read = readers.train_step_device_ms

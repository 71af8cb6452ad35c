"""The states' share of a decode step's least HBM bytes: 2 x a slot's state
bytes x the slots a step of the window advanced (``serve.chunk``:
``power_slots_advanced``) / those and every matmul weight once.
"""

from benchmarks.lib import power_names

read = power_names.state_bytes_share

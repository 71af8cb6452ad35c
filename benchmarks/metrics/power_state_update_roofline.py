"""Least time of a step's power-retention state update (each advanced
slot's symmetric-square state and normaliser read once and written once a
layer, at D = 8,256 rows a key/value head, at peak) / the measured time of
the update's ops a step.
"""

from benchmarks.lib import power_names

read = power_names.state_update_roofline

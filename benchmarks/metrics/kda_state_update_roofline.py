"""Least time of a step's delta-rule state update (each advanced slot's
matrix state read once and written once a KDA layer, at peak) / the measured
time of the update's ops a step.
"""

from benchmarks.lib import kda_names

read = kda_names.state_update_roofline

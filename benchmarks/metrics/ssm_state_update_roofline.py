"""Least time of a step's recurrent-state update (each advanced slot's
state read once and written once, at peak) / the measured time of the
update's ops a step.
"""

from benchmarks.lib import ssm_names

read = ssm_names.state_update_roofline

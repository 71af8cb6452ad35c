"""Least time of a step's recurrent-state update (each advanced slot's
128 x 64 x 128 float32 state read once and written once a Mamba block, at
peak: ``lib/nemotron_flops.py``) / the measured time of the update's ops a
step.
"""

from benchmarks.lib import nemotron_names

read = nemotron_names.state_update_roofline

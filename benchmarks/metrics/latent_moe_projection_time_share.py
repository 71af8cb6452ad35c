"""Own device time of the ops under scope ``latent_proj`` (the projections
into and out of the experts' latent, around the dispatch and the combine) /
device time of the decode programs.
"""

from benchmarks.lib import nemotron_names

read = nemotron_names.projection_time_share

"""Device time of a KDA layer's convolutions, norms, decay, beta and output
gate (scope ``kda_gates``) in the prefill and decode programs / device time
of both.
"""

from benchmarks.lib import kda_names

read = kda_names.conv_gate_time_share

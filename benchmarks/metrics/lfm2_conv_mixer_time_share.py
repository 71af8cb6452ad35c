"""Own device time of the ops under scopes ``conv_proj``, ``short_conv`` and
``conv_out`` (a gated short-convolution mixer: in-projection, the gate and
the 3-tap update of the conv state, out-projection) / device time of the
decode programs.  ``batch.decode_projection_time_share``'s fixed families do
not count these scopes.
"""

from benchmarks.lib import lfm2_names

read = lfm2_names.scope_time_share(*lfm2_names.CONV_SCOPES)

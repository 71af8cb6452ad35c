"""Least time of a step's latent attention (every latent row a live slot
attends: 1,152 bytes once and 2 x 128 x (576 + 512) FLOPs a layer, the
LARGER of the two floors at peak -- this kernel sits on the v5e's ridge) /
the measured time of the ``mla_decode_attention`` kernel a step.
"""

from benchmarks.lib import mla_names

read = mla_names.decode_attention_roofline

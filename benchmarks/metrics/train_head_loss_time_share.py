"""Own device time of the ops under scope ``head_loss`` (final norm, logits,
loss, forward and backward) / device time of the steps.
"""

from benchmarks.lib import scope_names

read = scope_names.time_share("train", "head_loss")

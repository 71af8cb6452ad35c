"""tokens/s per chip x afmoe_flops.train_flops_per_token (the layers held: gated
attention inside each layer's mask, dense SwiGLU, router, shared expert, one
expected held assignment a token, the slice's head; x 3, no recompute) / the
chip's bf16 peak.
"""

from benchmarks.lib import afmoe_names

read = afmoe_names.train_mfu

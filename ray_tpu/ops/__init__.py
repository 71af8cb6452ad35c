"""TPU kernel library (Pallas).

The reference delegates hot ops to cuDNN/torch kernels; here the hot
path is owned directly: flash attention (fwd+bwd, GQA-aware), ring
attention for sequence/context parallelism over the ICI ring, decode
attention (one query a slot against the stacked K/V cache where it lies,
each row read only as far as it is long: the serve programs' decode
step), and the building blocks the model zoo needs.  All kernels run in
interpret mode on CPU so the simulated-mesh test suite exercises them.
"""

from .flash_attention import flash_attention, flash_attention_causal
from .ring_attention import ring_attention, ring_attention_causal

__all__ = [
    "flash_attention",
    "flash_attention_causal",
    "ring_attention",
    "ring_attention_causal",
]

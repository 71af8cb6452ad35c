"""FLOPs inside the selection of the prompts prefilled (query t attends
``min(t + 1, topk)`` keys) at the bf16 peak / the measured time of the
``sparse_prefill_attention`` kernel's calls (the flash forward with a mask
that is data: causal tiles computed dense and masked).
"""

from benchmarks.lib import dsa_names

read = dsa_names.sparse_prefill_attention_roofline

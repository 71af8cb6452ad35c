"""FLOPs inside the causal mask of the prompts prefilled at the true widths
(q/k 192, v 128), at the bf16 peak / the measured time of the expanded
path's ``flash_prefill_attention`` calls.
"""

from benchmarks.lib import mla_names

read = mla_names.prefill_attention_roofline

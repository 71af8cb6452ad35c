"""FLOPs inside the causal mask and the window layers' bands of the prompts
prefilled, at the bf16 peak / the measured time of the
``flash_prefill_attention`` kernel's calls.
"""

from benchmarks.lib import swa_names

read = swa_names.prefill_attention_roofline

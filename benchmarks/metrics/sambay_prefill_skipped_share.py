"""serve.prefill_group: positions x layers the prefills did not compute (the
layers after the K/V layer run at each row's last position alone) /
positions x layers of the groups.
"""

from benchmarks.lib import sambay_names

read = sambay_names.prefill_skipped_share

"""Device time of the Mamba-1 selective scan (scope ``mamba1_scan``: XLA's
loop over the positions, no kernel of its own) / device time of the prefill
programs.
"""

from benchmarks.lib import sambay_names

read = sambay_names.ssm_scan_time_share

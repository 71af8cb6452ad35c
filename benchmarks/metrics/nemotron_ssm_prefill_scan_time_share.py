"""Own device time of the ops under scope ``ssm_scan`` (the chunked Mamba-2
scan, eight groups of B and C) / device time of the prefill programs.
"""

from benchmarks.lib import nemotron_names

read = nemotron_names.prefill_scan_time_share

"""Device time of the chunked state-space scan's ops (told by their
arrays: a chunk's ``[heads, Q, Q]`` matrices, the chunk and final states; by
the scope ``ssm_scan`` where the configuration's file says so) / device time
of the prefill programs.
"""

from benchmarks.lib import ssm_names

read = ssm_names.prefill_scan_time_share

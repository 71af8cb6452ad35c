"""Device time of differential attention's combination (scope
``diff_combine``: two softmaxes' results subtracted and normed, 16 layers) /
device time of the decode programs.
"""

from benchmarks.lib import sambay_names

read = sambay_names.diff_combine_time_share

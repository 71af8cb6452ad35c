"""Per request of >= 2 tokens: (completion - first token) / (tokens - 1);
median over the requests due in the window.  The median, not the 90th
percentile: that one is set by the shortest answers, whose last token
waits for the decode chunk's harvest, and spread up to 5% between runs of
the same code (PERF.md section 6); it is the per-layer ``tpot_p90_ms``.
"""

from benchmarks.lib import readers

read = readers.tpot_percentile(50)

"""Per request due in the window: (sent - due) + the engine's ttft_ms; 90th
percentile; a failed request counts as the worst.  Per-layer, not
end-to-end: with the ~160 requests a window holds it spread 2.8-5.4%
between runs of the same code, too wide for the 10% a bound may be
(PERF.md section 6).
"""

from benchmarks.lib import readers

read = readers.ttft_percentile(90)

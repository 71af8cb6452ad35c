"""Per request due in the window: (sent - due) + the engine's ttft_ms; 90th
percentile; a failed request counts as the worst.  Per-layer, not
end-to-end: with ~160 requests a window it spread 2.8-5.4% between runs
of the same code, with ~360 (the cell re-cut in PR 25) 3.9% and 4.7%,
over the 3% asked of it before it is held (PERF.md section 2).
"""

from benchmarks.lib import readers

read = readers.ttft_percentile(90)

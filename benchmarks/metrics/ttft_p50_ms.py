"""Median time to first token: how late the generator sent + the engine's
own ttft_ms.
"""

from benchmarks.lib import readers

read = readers.ttft_percentile(50)

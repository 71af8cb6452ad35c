"""Sum of cache_fetch_s over the xla_compile spans before the window opens:
the seconds of a start spent reading compiled programs back from the
persistent compilation cache (0 in a checkout's first run, which compiles).
"""

from benchmarks.lib import start_spans

read = start_spans.reader("cache_fetch_s")

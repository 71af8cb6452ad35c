"""Sum of self time of serve.warm_program: a warmed call's host seconds that no
xla_trace / xla_lower / xla_compile span covers (the executable's load, the
arguments' transfer, the dispatch).
"""

from benchmarks.lib import start_spans

read = start_spans.reader("warm_unnamed_s")

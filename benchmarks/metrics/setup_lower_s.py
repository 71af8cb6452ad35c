"""Sum of xla_lower before the window opens: jaxpr -> MLIR module of every program
first called during the start (the program's compile listener).
"""

from benchmarks.lib import start_spans

read = start_spans.reader("phase_s", "xla_lower")

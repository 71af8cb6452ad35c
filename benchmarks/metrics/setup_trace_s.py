"""Sum of xla_trace before the window opens: the Python trace (function -> jaxpr)
of every jitted function first called during the start, nested jits inside
their caller's (the program's compile listener, jax's own start and end).
"""

from benchmarks.lib import start_spans

read = start_spans.reader("phase_s", "xla_trace")

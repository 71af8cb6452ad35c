"""Process start (t_open - setup_s) -> serve.engine_start opens: imports, chip
start, ray_tpu.init(), the benchmark's weights, serve.run up to the replica's
constructor.
"""

from benchmarks.lib import start_spans

read = start_spans.reader("before_engine_s")

"""Output tokens produced inside the window / window seconds (closed
loop): each token counted where it was produced, every request in flight
at the close awaited (``loadgen.Log.tokens_in_window``).
"""

from benchmarks.lib import readers

read = readers.serve_output_tokens_per_s

"""serve.chunk: rows of the busiest expert of a layer / mean rows per
expert, the median over the chunks launched in the window (1 = even).
"""

from benchmarks.lib import moe_names

read = moe_names.load_imbalance

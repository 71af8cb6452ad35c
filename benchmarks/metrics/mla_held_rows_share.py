"""serve.chunk: (token, expert) assignments that landed on the experts held
here / all the router made (``expert_rows`` + ``expert_rows_elsewhere``),
over the window's chunks, in %.
"""

from benchmarks.lib import mla_names

read = mla_names.held_rows_share

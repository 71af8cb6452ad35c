"""serve.chunk: cache positions the live rows held at launch
(``kv_positions_attended``) / ``max_slots`` x the attended length bucket
(``kv_positions_bucket``), over the chunks launched in the window, in %:
the share of every slot's whole bucket that the decode attention has to
read.  None where the program's chunks carry neither (before PR 29).
"""

from benchmarks.lib import program_spans


def read(obs):
    got = program_spans.collect(obs)
    chunks = [c for c in (got.chunks if got else [])
              if c.get("kv_positions_bucket")]
    if not chunks:
        return None
    return 100.0 * sum(c["kv_positions_attended"] for c in chunks) \
        / sum(c["kv_positions_bucket"] for c in chunks)

"""Own device time of the ops under scope ``attention`` (GQA 32/8 at head 64:
not whole lanes, so XLA's ``_cache_attend`` path with its staging copy of
every slot's bucket, not the Mosaic kernel) / device time of the decode
programs.
"""

from benchmarks.lib import lfm2_names

read = lfm2_names.scope_time_share("attention")

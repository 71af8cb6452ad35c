"""How late the generator itself sent: 99th percentile of sent - due.
"""

from benchmarks.lib import readers
from benchmarks.lib.runtime import percentile


def read(obs):
    return percentile(readers.loadgen_lag_ms(obs), 99)

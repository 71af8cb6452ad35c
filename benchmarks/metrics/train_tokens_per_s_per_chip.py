"""Whole steps finished in the window x batch x seq / seconds between the
waits that bracket them / chips.
"""

from benchmarks.lib import readers

read = readers.train_tokens_per_s_per_chip

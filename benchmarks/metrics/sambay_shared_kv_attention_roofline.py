"""Least time of a step's eight reads of the shared K/V pool (rows attended x
5,120 B a reading layer at the HBM peak, the same count whatever implements
the reads) / the measured time of the ops under ``cross_attention``.
"""

from benchmarks.lib import sambay_names

read = sambay_names.shared_kv_attention_roofline

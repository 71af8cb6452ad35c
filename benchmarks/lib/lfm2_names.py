"""The readers of the two ``lfm2_*`` metrics: a short-convolution model's
mixers in a decode step, by the program's own scopes (its experts are
``lib/moe_names.py``'s, its step's floor ``lib/lfm2_flops.py``'s).

Nothing here is found by an op's shape: the time shares are the own
device seconds of the ops the program traced under its scopes
(``conv_proj`` / ``short_conv`` / ``conv_out`` of
``ray_tpu/models/shortconv.py``; ``attention``, which at head 64 is XLA's
``llama._cache_attend`` path with its staging copies) over the device
seconds of the ``jit_decode_k`` runs, through ``scope_names.split``.  What
a step had to do comes from the program's spans (``serve.chunk``:
``expert_rows``, ``experts_touched``, ``state_rows_updated``).

A configuration without a ``conv`` in its ``layer_types`` is not looked
at; a program without such scopes or span attributes (the commit before
the model) matches nothing, and the readers return None.
"""

from __future__ import annotations

import statistics
from typing import Optional, Tuple

from . import program_spans, scope_names

CONV_SCOPES = ("conv_proj", "short_conv", "conv_out")


def _lfm2(obs) -> bool:
    return "conv" in obs["cell"].config.get("layer_types", ())


def chunk_medians(obs) -> Optional[Tuple[float, float, float]]:
    """Medians over the window's ``serve.chunk`` spans of (expert rows a
    step, (layer, expert) pairs touched a step, slots whose conv states a
    step advanced): the program's own count of what a step had to do."""
    got = program_spans.collect(obs) if _lfm2(obs) else None
    chunks = [c for c in (got.chunks if got else [])
              if c.get("expert_rows") and c.get("state_rows_updated")]
    if not chunks:
        return None
    return tuple(statistics.median(c[key] / c["k"] for c in chunks)
                 for key in ("expert_rows", "experts_touched",
                             "state_rows_updated"))


def scope_time_share(*scopes: str):
    """``scope_names.scopes_time_share`` of the decode programs, for a
    configuration with short-convolution layers."""
    return scope_names.scopes_time_share(*scopes, applies=_lfm2)

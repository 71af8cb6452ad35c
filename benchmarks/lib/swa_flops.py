"""Operations and bytes the ALGORITHM of a decoder needs whose layers mix
full and sliding-window attention (SmallThinker), from shapes alone.  The
yardstick's own arithmetic: nothing here is read from the program.

A configuration is the dict of ``benchmarks/configs/<name>.json``:
``sliding_window_layout`` (1: the layer's queries see their last
``sliding_window_size`` keys, their own among them; 0: every key), of
which the first ``num_hidden_layers`` entries are the layers that run.  A
multiply-add counts as 2 FLOPs.  The layers' weights are
``lib/moe_flops.py``'s (every layer has experts, none is shared).
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Tuple

from . import moe_flops


def layer_counts(c: Dict[str, Any]) -> Tuple[int, int]:
    """(full layers, window layers) among the layers that run."""
    layout = c["sliding_window_layout"][:c["num_hidden_layers"]]
    return layout.count(0), layout.count(1)


def kv_bytes_per_key(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V of ONE position of ONE layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * dtype_bytes


def keys_read(c: Dict[str, Any], lengths: Iterable[float]
              ) -> Tuple[float, float]:
    """Keys one decode step has to read over all layers for rows holding
    ``lengths`` positions: (in the full layers, in the window layers, a
    row's ``min(length, window)`` each)."""
    full, window = layer_counts(c)
    w = c["sliding_window_size"]
    lengths = list(lengths)
    return (full * float(sum(lengths)),
            window * float(sum(min(n, w) for n in lengths)))


def decode_attention_bytes(c: Dict[str, Any], lengths: Iterable[float],
                           dtype_bytes: int = 2) -> float:
    """Least HBM traffic of a step's attention: every key a row attends,
    K and V once."""
    return sum(keys_read(c, lengths)) * kv_bytes_per_key(c, dtype_bytes)


def decode_attention_flops(c: Dict[str, Any],
                           lengths: Iterable[float]) -> float:
    """q.k and p.v over every key a row attends, every query head."""
    return 2.0 * 2.0 * sum(keys_read(c, lengths)) \
        * c["num_attention_heads"] * c["head_dim"]


def decode_step_bytes(c: Dict[str, Any], experts_touched: float,
                      lengths: Iterable[float],
                      dtype_bytes: int = 2) -> float:
    """Least HBM traffic of ONE decode step: attention, router and head
    weights once (the embedding is gathered row-wise), the three matrices
    of each (layer, expert) pair TOUCHED in the step, and each live row's
    keys and values once -- a window layer's at ``min(length, window)``."""
    dense = c["num_hidden_layers"] * moe_flops.dense_matmul_params_per_layer(c) \
        + c["hidden_size"] * c["vocab_size"]
    return (dense + experts_touched * moe_flops.expert_params(c)) \
        * dtype_bytes + decode_attention_bytes(c, lengths, dtype_bytes)


def decode_step_flops(c: Dict[str, Any], lengths: Iterable[float],
                      expert_rows: float) -> float:
    lengths = list(lengths)
    dense = c["num_hidden_layers"] * moe_flops.dense_matmul_params_per_layer(c) \
        + c["hidden_size"] * c["vocab_size"]
    return 2.0 * dense * len(lengths) \
        + decode_attention_flops(c, lengths) \
        + moe_flops.expert_matmul_flops(c, expert_rows)


def band_pairs(length: float, window: int = 0) -> float:
    """(query, key) pairs a causal prompt of ``length`` positions
    attends: every key at or before its query, the last ``window`` of
    them where one is given."""
    if not window or length <= window:
        return length * (length + 1) / 2.0
    return window * (window + 1) / 2.0 + (length - window) * float(window)


def prefill_attention_flops(c: Dict[str, Any], length: float) -> float:
    """q.k and p.v of ONE prompt's attention over all layers, inside the
    causal mask and each window layer's band: what has to be computed,
    not what a tiled kernel computes."""
    full, window = layer_counts(c)
    pairs = full * band_pairs(length) \
        + window * band_pairs(length, c["sliding_window_size"])
    return 2.0 * 2.0 * pairs * c["num_attention_heads"] * c["head_dim"]


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one decode step (dense weights once, the experts
    the step touched once, each live row's keys once, a ring layer's at
    ``min(length, window)``: HBM bytes or FLOPs at peak, the larger);
    None where the run says neither."""
    from . import moe_names, swa_names   # what the run observed

    lengths = swa_names._traced_lengths(obs)
    medians = moe_names.chunk_medians(obs)
    if lengths is None or medians is None:
        return None
    rows, touched, _imbalance = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    return max(
        decode_step_bytes(cfg, touched, lengths) / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lengths, rows) / peaks["bf16_flops_per_s"])

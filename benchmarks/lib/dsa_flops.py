"""Parameters, operations and bytes the ALGORITHM of a decoder with a
learned sparse-attention indexer in front of GQA, and experts in every
layer, needs, from shapes alone.  The yardstick's own arithmetic: nothing
here is read from the program.

A configuration is the dict of ``benchmarks/configs/<name>.json``
(published key names): ``sa_config`` sizes the indexer
(``indexer_num_heads`` index queries of ``indexer_head_dim`` and ONE index
key of that width a token, ``topk`` keys attended a query); every layer has
``num_experts`` experts of ``moe_intermediate_size`` (``intermediate_size``
is the published width of a dense FFN no layer has, which is why
``lib/moe_flops.py``'s whole-step counts, that read it as an expert's, are
not this model's; its counts of the grouped matmuls alone are, and are used
here).  A multiply-add counts as 2 FLOPs; bytes are ``dtype_bytes`` a value
(bfloat16).  ``decode_step_least_s`` is the floor the configuration's file
names under ``roofline``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional

# the grouped matmuls' own counts are every expert configuration's
from .moe_flops import expert_matmul_flops, expert_params


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """W_q and W_o (h x heads x head_dim), W_k and W_v (h x kv x
    head_dim)."""
    h, d = c["hidden_size"], c["head_dim"]
    return 2 * h * c["num_attention_heads"] * d \
        + 2 * h * c["num_key_value_heads"] * d


def indexer_params(c: Dict[str, Any]) -> int:
    """W_qI (h -> heads x dim), W_kI (h -> dim), W_w (h -> heads)."""
    sa = c["sa_config"]
    heads, dim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return c["hidden_size"] * (heads * dim + dim + heads)


def router_params(c: Dict[str, Any]) -> int:
    return c["hidden_size"] * c["num_experts"]


def layer_params(c: Dict[str, Any]) -> int:
    """A whole layer: attention's four matrices and its q and k norms (one
    head wide), the indexer, the two block norms, the router, the
    experts."""
    return attention_matmul_params(c) + 2 * c["head_dim"] \
        + indexer_params(c) + 2 * c["hidden_size"] + router_params(c) \
        + c["num_experts"] * expert_params(c)


def parameters(c: Dict[str, Any], layers: int = 0) -> int:
    """Every parameter at ``layers`` layers (the file's own where 0): the
    embedding, the untied head, the final norm, the layers."""
    return 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"] \
        + (layers or c["num_hidden_layers"]) * layer_params(c)


# ------------------------------------------------------------- the cache
def kv_bytes_per_position(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V of ONE position over the layers."""
    return c["num_hidden_layers"] * 2 * c["num_key_value_heads"] \
        * c["head_dim"] * dtype_bytes


def index_key_bytes_per_position(c: Dict[str, Any],
                                 dtype_bytes: int = 2) -> int:
    """The index key of ONE position over the layers."""
    return c["num_hidden_layers"] * c["sa_config"]["indexer_head_dim"] \
        * dtype_bytes


def slot_bytes(c: Dict[str, Any], positions: int) -> int:
    """What a slot of ``positions`` costs beside the weights."""
    return positions * (kv_bytes_per_position(c)
                        + index_key_bytes_per_position(c))


def selected(c: Dict[str, Any], length: float) -> float:
    """Keys a query attends that has ``length`` keys before and at it."""
    return min(length, c["sa_config"]["topk"])


# ----------------------------------------------------------- decode step
def step_matmul_params(c: Dict[str, Any]) -> int:
    """What every token of a step multiplies by, whatever it is routed
    to: each layer's attention matrices, index projections and router,
    and the head; the embedding is gathered row-wise."""
    return c["hidden_size"] * c["vocab_size"] + c["num_hidden_layers"] * (
        attention_matmul_params(c) + indexer_params(c) + router_params(c))


def decode_step_bytes(c: Dict[str, Any], experts_touched: float,
                      lengths: Iterable[float],
                      dtype_bytes: int = 2) -> float:
    """Least HBM traffic of ONE decode step: every non-expert matmul
    weight once, the three matrices of each (layer, expert) pair TOUCHED in
    the step, every index key a live row holds once, and K and V of the
    ``min(length, topk)`` positions it attends."""
    lengths = list(lengths)
    return (step_matmul_params(c) + experts_touched * expert_params(c)) \
        * dtype_bytes \
        + sum(lengths) * index_key_bytes_per_position(c, dtype_bytes) \
        + sum(selected(c, n) for n in lengths) \
        * kv_bytes_per_position(c, dtype_bytes)


def index_score_flops(c: Dict[str, Any], pairs: float) -> float:
    """``pairs`` (query, key) index scores in every layer: the heads' dot
    products (the ReLU, weights and sum over heads are 3 more a head)."""
    sa = c["sa_config"]
    return c["num_hidden_layers"] * pairs * sa["indexer_num_heads"] \
        * (2 * sa["indexer_head_dim"] + 3)


def attention_flops(c: Dict[str, Any], pairs: float) -> float:
    """q.k and p.v of every head over ``pairs`` (query, key) pairs, in
    every layer."""
    return c["num_hidden_layers"] * pairs * 2 * 2 \
        * c["num_attention_heads"] * c["head_dim"]


def decode_step_flops(c: Dict[str, Any], lengths: Iterable[float],
                      expert_rows: float) -> float:
    lengths = list(lengths)
    return 2.0 * step_matmul_params(c) * len(lengths) \
        + index_score_flops(c, sum(lengths)) \
        + attention_flops(c, sum(selected(c, n) for n in lengths)) \
        + expert_matmul_flops(c, expert_rows)


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one decode step (every non-expert matmul weight
    once, three matrices of each (layer, expert) touched, every index key
    a live row holds, K and V of the ``min(length, topk)`` rows it
    attends: HBM bytes or the step's FLOPs at peak, the larger); None
    where the run says neither."""
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


# --------------------------------------------------------------- prefill
def selected_pairs(c: Dict[str, Any], prompt: float) -> float:
    """(query, key) pairs a prompt's attention has to compute: query t
    attends ``min(t + 1, topk)`` keys."""
    k = min(prompt, c["sa_config"]["topk"])
    return k * (k + 1) / 2 + (prompt - k) * k


def prefill_attention_flops(c: Dict[str, Any], prompt: float) -> float:
    """Attention FLOPs inside the selection, all layers."""
    return attention_flops(c, selected_pairs(c, prompt))

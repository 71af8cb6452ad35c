"""Operations and bytes the ALGORITHM of one TRAIN step of a decoder needs
whose layers mix full and sliding-window attention behind an output gate,
with leading dense layers and, after them, a shared expert beside a held
share of routed experts (Trinity-Mini / ``afmoe``), from shapes alone.  The
yardstick's own arithmetic: nothing here is read from the program.

A configuration is the dict of ``benchmarks/configs/<name>.json`` (the
published keys; ``share`` says which of the routed experts this chip
holds).  A multiply-add counts as 2 FLOPs.  ``lib/flops.py`` counts every
layer a dense SwiGLU and every layer's attention the whole causal square;
here a layer is what its entry of ``layer_types`` and its place before or
after ``num_dense_layers`` say.  A configuration's file names this module
under ``train_counts`` and ``readers.train_mfu`` /
``readers.flash_attention_roofline`` divide by its counts; what the grouped
matmuls alone must do in a step is ``lib/moe_flops.py``'s
(``expert_matmul_train_flops`` / ``_bytes``), every configuration's with
experts.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

from .flops import flash_train_bytes  # noqa: F401  (the band moves no byte)
from .swa_flops import band_pairs


def layer_counts(c: Dict[str, Any]) -> Tuple[int, int]:
    """(full layers, sliding layers) among the layers that run."""
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


def attention_params(c: Dict[str, Any]) -> int:
    """One layer's attention matmuls: W_q, W_k, W_v, the gate's W_g and
    W_o."""
    h = c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return h * q + 2 * h * kv + h * q + q * h


def expert_params(c: Dict[str, Any]) -> int:
    """The three matrices of ONE expert (routed or shared)."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def held_share(c: Dict[str, Any]) -> float:
    """The share of a token's routed assignments that land on this chip
    under even routing: experts held / experts the router scores."""
    share = c.get("share")
    if not share:
        return 1.0
    return share["experts_held"] / share["num_experts_published"]


def held_rows_per_token(c: Dict[str, Any]) -> float:
    """Expected rows the held experts compute a token and layer."""
    return c["num_experts_per_tok"] * held_share(c)


def matmul_params_per_token(c: Dict[str, Any]) -> float:
    """Weights a token is multiplied by in one forward pass: attention
    with its gate in every layer, the dense layers' SwiGLU, an expert
    layer's router, shared expert(s) and expected held assignments, and
    the head over this chip's rows; not the embedding (a gather), not the
    norms."""
    layers, dense = c["num_hidden_layers"], c["num_dense_layers"]
    h = c["hidden_size"]
    experts_scored = (c.get("share") or {}).get("num_experts_published",
                                                c["num_experts"])
    expert_layer = h * experts_scored + expert_params(c) * (
        c["num_shared_experts"] + held_rows_per_token(c))
    return (layers * attention_params(c)
            + dense * 3 * h * c["intermediate_size"]
            + (layers - dense) * expert_layer + h * c["vocab_size"])


def attention_pairs(c: Dict[str, Any], seq: int) -> float:
    """(query, key) pairs ONE sequence attends over all layers: the causal
    triangle in a full layer, the band of ``sliding_window`` keys in a
    sliding one."""
    full, sliding = layer_counts(c)
    return full * band_pairs(seq) \
        + sliding * band_pairs(seq, c["sliding_window"])


def flops_per_pair(c: Dict[str, Any]) -> int:
    """q.k and p.v of one (query, key) pair, every query head."""
    return 2 * 2 * c["num_attention_heads"] * c["head_dim"]


def forward_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    return 2.0 * matmul_params_per_token(c) \
        + attention_pairs(c, seq) * flops_per_pair(c) / seq


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """Forward + backward (= 3 x forward) a trained token.  No
    recomputation, no embedding lookup, no optimizer, no balance update."""
    return 3.0 * forward_flops_per_token(c, seq)


def flash_train_flops(c: Dict[str, Any], batch: int, seq: int) -> float:
    """What the flash kernels of one step must compute inside the masks:
    the forward once and the backward's five matmuls (the scores again;
    dV, dP, dQ, dK) = 2.5 x forward.  The kernels' own recompute of the
    scores is part of the flash algorithm and is counted."""
    return 3.5 * batch * attention_pairs(c, seq) * flops_per_pair(c)

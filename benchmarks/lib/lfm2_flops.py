"""Parameters, operations and bytes the ALGORITHM of an LFM2-shaped decoder
needs (gated short-convolution layers beside GQA layers, leading dense
layers, then experts under a sigmoid router with a selection bias), from
shapes alone.  The yardstick's own arithmetic: nothing here is read from
the program.

A configuration is the dict of ``benchmarks/configs/<name>.json``
(published key names): ``layer_types`` names each layer ``conv`` or
``full_attention``; the first ``num_dense_layers`` carry a dense SwiGLU of
``intermediate_size``, the others ``num_experts`` experts of
``moe_intermediate_size`` (which is why ``lib/moe_flops.py``'s whole-step
counts, that read the first as the second, are not this model's; its
counts of the grouped matmuls alone are, and are used here).  A
multiply-add counts as 2 FLOPs; bytes are ``dtype_bytes`` a value
(bfloat16).  ``decode_step_least_s`` is the floor the configuration's file
names under ``roofline``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional, Sequence

# the grouped matmuls' own counts are every expert configuration's
from .moe_flops import expert_matmul_flops, expert_params


def head_dim(c: Dict[str, Any]) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def kinds(c: Dict[str, Any], layer_types: Sequence[str] = ()) -> list:
    return list(layer_types or c["layer_types"])


def conv_mixer_params(c: Dict[str, Any]) -> int:
    """W_in (h -> 3h), the taps (conv_L_cache x h), W_out (h -> h)."""
    h = c["hidden_size"]
    return h * 3 * h + c["conv_L_cache"] * h + h * h


def attention_matmul_params(c: Dict[str, Any]) -> int:
    """W_q and W_o (h x heads x head_dim), W_k and W_v (h x kv x
    head_dim)."""
    h, d = c["hidden_size"], head_dim(c)
    return 2 * h * c["num_attention_heads"] * d \
        + 2 * h * c["num_key_value_heads"] * d


def attention_params(c: Dict[str, Any]) -> int:
    """The four matrices and the q and k norms' weights, one head wide."""
    return attention_matmul_params(c) + 2 * head_dim(c)


def mixer_params(c: Dict[str, Any], kind: str) -> int:
    return conv_mixer_params(c) if kind == "conv" else attention_params(c)


def dense_ffn_params(c: Dict[str, Any]) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def router_params(c: Dict[str, Any]) -> int:
    """The router's matrix and, with ``use_expert_bias``, its bias."""
    return c["hidden_size"] * c["num_experts"] \
        + (c["num_experts"] if c["use_expert_bias"] else 0)


def layer_params(c: Dict[str, Any], kind: str, dense: bool) -> int:
    """A whole layer: its mixer, its two block norms, its FFN."""
    ffn = dense_ffn_params(c) if dense else \
        router_params(c) + c["num_experts"] * expert_params(c)
    return mixer_params(c, kind) + 2 * c["hidden_size"] + ffn


def parameters(c: Dict[str, Any], layer_types: Sequence[str] = ()) -> int:
    """Every parameter of the layers ``layer_types`` names (the file's own
    where empty): the tied embedding, the final norm, the layers."""
    return c["vocab_size"] * c["hidden_size"] + c["hidden_size"] + sum(
        layer_params(c, kind, i < c["num_dense_layers"])
        for i, kind in enumerate(kinds(c, layer_types)))


def layers_of(c: Dict[str, Any], kind: str) -> int:
    return kinds(c).count(kind)


def expert_layers(c: Dict[str, Any]) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


# ------------------------------------------------------ cache and state
def kv_bytes_per_position(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V of ONE position over the attention layers."""
    return layers_of(c, "full_attention") * 2 \
        * c["num_key_value_heads"] * head_dim(c) * dtype_bytes


def conv_state_bytes_per_slot(c: Dict[str, Any],
                              dtype_bytes: int = 2) -> int:
    """One slot's conv states over the conv layers: ``u`` at its last
    ``conv_L_cache - 1`` positions (8 KB a layer)."""
    return layers_of(c, "conv") * (c["conv_L_cache"] - 1) \
        * c["hidden_size"] * dtype_bytes


def slot_bytes(c: Dict[str, Any], positions: int) -> int:
    """What a slot of ``positions`` costs beside the weights."""
    return positions * kv_bytes_per_position(c) \
        + conv_state_bytes_per_slot(c)


# ----------------------------------------------------------- decode step
def step_matmul_params(c: Dict[str, Any]) -> int:
    """What every token of a step multiplies by, whatever it is routed
    to: every mixer's matrices and taps, the dense layers' FFNs, the
    routers, the tied head; the embedding is gathered row-wise."""
    total = c["hidden_size"] * c["vocab_size"]
    for i, kind in enumerate(kinds(c)):
        total += conv_mixer_params(c) if kind == "conv" \
            else attention_matmul_params(c)
        total += dense_ffn_params(c) if i < c["num_dense_layers"] \
            else c["hidden_size"] * c["num_experts"]
    return total


def decode_attention_flops(c: Dict[str, Any],
                           lengths: Iterable[float]) -> float:
    """q.k and p.v of every head over every key a live row holds, in
    every attention layer."""
    return layers_of(c, "full_attention") * float(sum(lengths)) \
        * 2 * 2 * c["num_attention_heads"] * head_dim(c)


def decode_step_bytes(c: Dict[str, Any], experts_touched: float,
                      lengths: Iterable[float], rows_advanced: float,
                      dtype_bytes: int = 2) -> float:
    """Least HBM traffic of ONE decode step: every non-expert matmul
    weight once, the three matrices of each (layer, expert) pair TOUCHED
    in the step, each live row's K and V as far as it is long, and the
    conv states of the ``rows_advanced`` slots read and written once."""
    return (step_matmul_params(c) + experts_touched * expert_params(c)) \
        * dtype_bytes \
        + float(sum(lengths)) * kv_bytes_per_position(c, dtype_bytes) \
        + 2 * rows_advanced * conv_state_bytes_per_slot(c, dtype_bytes)


def decode_step_flops(c: Dict[str, Any], lengths: Iterable[float],
                      expert_rows: float) -> float:
    lengths = list(lengths)
    return 2.0 * step_matmul_params(c) * len(lengths) \
        + decode_attention_flops(c, lengths) \
        + expert_matmul_flops(c, expert_rows)


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one decode step (every non-expert matmul weight
    once, three matrices of each (layer, expert) touched, the K/V in
    flight as far as each row is long, the conv states of the slots
    advanced read and written once: HBM bytes or the step's FLOPs at
    peak, the larger); None where the run says neither."""
    from . import lfm2_names, swa_names   # what the run observed

    lengths = swa_names._traced_lengths(obs)
    medians = lfm2_names.chunk_medians(obs)
    if lengths is None or medians is None:
        return None
    rows, touched, advanced = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    return max(
        decode_step_bytes(cfg, touched, lengths, advanced)
        / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lengths, rows) / peaks["bf16_flops_per_s"])

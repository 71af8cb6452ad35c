"""Operations and bytes the ALGORITHM of a hybrid decoder with Mamba-2
layers needs, from shapes alone (``lib/flops.py`` counts a dense decoder
only).  The yardstick's own arithmetic: nothing here is read from the
program.

A configuration is the dict of ``benchmarks/configs/<name>.json`` (the
published ``config.json`` keys: ``layer_types``, ``mamba_n_heads``,
``mamba_d_head``, ``mamba_d_state``, ``mamba_n_groups``, ``mamba_d_conv``;
``dtype.ssm_state`` names the type the recurrent state is stored in).  A
multiply-add counts as 2 FLOPs.  Every layer has the dense SwiGLU MLP.

The mixer's own shape (``shape``: what the state's bytes and the update's
operations are counted from) is every configuration's with such layers: a
file whose family spells the keys otherwise states it under ``ssm_shape``,
``{"heads": 128, "head_dim": 64, "state": 128, "groups": 8, "conv": 4,
"layers": 5}`` (``layers``: the Mamba layers held), and with ``"ops":
"scopes"`` that its program traces the update and the scan under the scopes
``ssm_state_update`` / ``ssm_scan`` (``lib/ssm_names.py``).

By hand, granite-4.0-h-micro: a Mamba mixer's in-projection is 2,048 x
(4,096 + 4,352 + 64) = 17,432,576 weights, its out-projection 4,096 x
2,048 = 8,388,608; a slot's recurrent state is 36 x 64 x 64 x 128 =
18,874,368 elements (37.7 MB in bfloat16), its conv state 36 x 3 x 4,352
= 470,016 (0.94 MB).
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


class Shape(NamedTuple):
    heads: int
    head_dim: int
    state: int
    groups: int
    conv: int       # taps
    layers: int     # Mamba layers


def shape(c: Dict[str, Any]) -> Optional[Shape]:
    """The mixer's shape: what the file states under ``ssm_shape``, else
    Granite's published keys; None for a configuration with no such
    layer."""
    stated = c.get("ssm_shape")
    if stated:
        return Shape(*(stated[k] for k in Shape._fields))
    if "mamba" not in c.get("layer_types", ()):
        return None
    return Shape(c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
                 c["mamba_n_groups"], c["mamba_d_conv"],
                 c["layer_types"].count("mamba"))


def layer_counts(c: Dict[str, Any]):
    """(attention layers, Mamba layers)."""
    kinds = c["layer_types"]
    return kinds.count("attention"), kinds.count("mamba")


def mamba_dims(c: Dict[str, Any]):
    """(d_inner, conv_dim, width of the in-projection [z | xBC | dt])."""
    s = shape(c)
    d_inner = s.heads * s.head_dim
    conv_dim = d_inner + 2 * s.groups * s.state
    return d_inner, conv_dim, d_inner + conv_dim + s.heads


def mixer_matmul_params(c: Dict[str, Any]) -> int:
    """The in- and out-projection of ONE Mamba mixer."""
    d_inner, _conv, in_dim = mamba_dims(c)
    return c["hidden_size"] * in_dim + d_inner * c["hidden_size"]


def attention_matmul_params(c: Dict[str, Any]) -> int:
    h = c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return h * q + 2 * h * kv + q * h


def matmul_params(c: Dict[str, Any]) -> int:
    """Weights a token is multiplied by in one forward pass: each layer's
    mixer and MLP, and the output head (the tied embedding read as a
    matrix); not the embedding lookup, not norms, conv or per-head
    vectors."""
    n_attn, n_mamba = layer_counts(c)
    h, f = c["hidden_size"], c["intermediate_size"]
    return (n_attn * attention_matmul_params(c)
            + n_mamba * mixer_matmul_params(c)
            + (n_attn + n_mamba) * 3 * h * f + h * c["vocab_size"])


def param_count(c: Dict[str, Any]) -> int:
    """Every parameter: the matmul weights, two norms a layer, a Mamba
    mixer's conv weight and bias, dt_bias, A_log, D and gated norm, the
    embedding (tied: counted once, as the head), the final norm."""
    n_attn, n_mamba = layer_counts(c)
    d_inner, conv_dim, _ = mamba_dims(c)
    s, h = shape(c), c["hidden_size"]
    nh = s.heads
    small = conv_dim * s.conv + conv_dim + 3 * nh + d_inner
    head = 0 if c["tie_word_embeddings"] else h * c["vocab_size"]
    return (matmul_params(c) + head + (n_attn + n_mamba) * 2 * h
            + n_mamba * small + h)


def state_bytes_per_slot(c: Dict[str, Any]) -> Dict[str, int]:
    """Bytes ONE slot's states hold over all Mamba layers: ``ssm`` (nh x
    hd x N a layer, in ``dtype.ssm_state``) and ``conv`` (the last
    d_conv - 1 inputs of conv_dim channels, in the serving type)."""
    s = shape(c)
    _d_inner, conv_dim, _ = mamba_dims(c)
    elements = s.heads * s.head_dim * s.state
    return {"ssm": s.layers * elements * _ITEMSIZE[c["dtype"]["ssm_state"]],
            "conv": s.layers * (s.conv - 1) * conv_dim
            * _ITEMSIZE[c["dtype"]["serve"]]}


def kv_bytes_per_token(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V rows of one position, over the ATTENTION layers."""
    n_attn, _ = layer_counts(c)
    return (2 * n_attn * c["num_key_value_heads"] * c["head_dim"]
            * dtype_bytes)


def state_update_bytes(c: Dict[str, Any], rows: float) -> float:
    """Least HBM traffic of one step's recurrent-state update: each of
    the ``rows`` slots it advances has its state read once and written
    once, in every Mamba layer."""
    return 2.0 * rows * state_bytes_per_slot(c)["ssm"]


def state_update_flops(c: Dict[str, Any], rows: float) -> float:
    """Per state element and row: decay x S, (dt x) x B, their sum, and
    the contraction with C (a multiply and an add): 5."""
    s = shape(c)
    return 5.0 * rows * s.layers * (s.heads * s.head_dim * s.state)


def decode_step_bytes(c: Dict[str, Any], rows: float,
                      context_tokens: float, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of ONE decode step: every matmul weight once,
    the recurrent AND conv states of the ``rows`` slots it advances read
    and written once, and each sequence's keys and values once
    (``context_tokens`` positions held by the batch in flight)."""
    per_slot = state_bytes_per_slot(c)
    return (matmul_params(c) * dtype_bytes
            + 2.0 * rows * (per_slot["ssm"] + per_slot["conv"])
            + context_tokens * kv_bytes_per_token(c, dtype_bytes))


def decode_step_flops(c: Dict[str, Any], batch: float,
                      context_tokens: float) -> float:
    """One decode step over ``batch`` sequences holding ``context_tokens``
    positions in all: the matmuls, QK^T and PV of the attention layers,
    the state update."""
    n_attn, _ = layer_counts(c)
    attn = (2 * 2 * context_tokens * c["num_attention_heads"]
            * c["head_dim"] * n_attn)
    return 2.0 * matmul_params(c) * batch + attn \
        + state_update_flops(c, batch)


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one decode step (every matmul weight once, the
    states of the slots it advances read and written once, the K/V of the
    batch in flight at the middle of the traced span: HBM bytes or FLOPs
    at peak, whichever is larger); None where the run says neither."""
    from . import readers, ssm_names   # what the run observed

    span = obs.get("trace_span")
    if not span or span[0] is None:
        return None
    rows = ssm_names.rows_a_step(obs)
    sequences, positions = readers.context_in_flight(
        obs, (span[0] + span[1]) / 2)
    if rows is None or not sequences:
        return None
    cfg, peaks = obs["cell"].config, obs["peaks"]
    return max(
        decode_step_bytes(cfg, rows, positions) / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, sequences, positions)
        / peaks["bf16_flops_per_s"])

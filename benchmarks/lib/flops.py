"""Operations and bytes the ALGORITHM needs, from shapes alone.  The
yardstick's own arithmetic: nothing here is read from the program.

A configuration is the dict of ``benchmarks/configs/<name>.json`` (the
published ``config.json`` keys).  A multiply-add counts as 2 FLOPs.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def _sizes(c: Dict[str, Any]):
    h = c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return h, q, kv, c["intermediate_size"], c["vocab_size"], \
        c["num_hidden_layers"]


def param_count(c: Dict[str, Any]) -> int:
    h, q, kv, f, v, layers = _sizes(c)
    per_layer = h * q + 2 * h * kv + q * h + 3 * h * f + 2 * h
    head = 0 if c["tie_word_embeddings"] else v * h
    return layers * per_layer + v * h + head + h


def matmul_params(c: Dict[str, Any]) -> int:
    """Weights a token is multiplied by in one forward pass: every
    projection and the output head; not the embedding lookup (a gather),
    not the norms."""
    h, q, kv, f, v, layers = _sizes(c)
    return layers * (h * q + 2 * h * kv + q * h + 3 * h * f) + h * v


def attention_flops_fwd(c: Dict[str, Any], seq: int,
                        causal: bool = True) -> float:
    """QK^T and PV of ONE sequence of ``seq`` tokens, all layers.  Causal
    attention needs half of the square (the diagonal is ~1/seq more)."""
    layers = c["num_hidden_layers"]
    heads, d = c["num_attention_heads"], c["head_dim"]
    full = 2 * 2 * seq * seq * heads * d
    return layers * full * (0.5 if causal else 1.0)


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """Forward + backward (= 3 x forward) per trained token: the matmuls
    including the head, plus causal attention.  No recomputation, no
    embedding lookup, no optimizer (elementwise)."""
    fwd = 2 * matmul_params(c) + attention_flops_fwd(c, seq) / seq
    return 3.0 * fwd


def flash_train_flops(c: Dict[str, Any], batch: int, seq: int) -> float:
    """What the flash kernels of one train step must compute: causal
    forward (QK^T, PV) once, and the backward's five matmuls (recompute
    QK^T; dV, dP, dQ, dK) = 2.5 x forward.  The kernels' own recompute of
    the scores is part of the flash algorithm and is counted."""
    return batch * attention_flops_fwd(c, seq) * 3.5


def kv_bytes_per_token(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """K and V rows of one position, all layers."""
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * dtype_bytes)


def weight_bytes(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Bytes of weights a decode step must read: every matmul weight once
    (the embedding table is gathered row-wise, one row a sequence)."""
    return matmul_params(c) * dtype_bytes


def decode_step_bytes(c: Dict[str, Any], context_tokens: int,
                      dtype_bytes: int = 2) -> float:
    """Least HBM traffic of ONE decode step over a batch whose sequences
    hold ``context_tokens`` positions in total: the weights once, and each
    sequence's keys and values once."""
    return weight_bytes(c, dtype_bytes) + \
        context_tokens * kv_bytes_per_token(c, dtype_bytes)


def decode_step_flops(c: Dict[str, Any], batch: int,
                      context_tokens: int) -> float:
    heads, d = c["num_attention_heads"], c["head_dim"]
    attn = 2 * 2 * context_tokens * heads * d * c["num_hidden_layers"]
    return 2.0 * matmul_params(c) * batch + attn


def flash_train_bytes(c: Dict[str, Any], batch: int, seq: int,
                      dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the flash kernels of one train step: forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv (the width-1 lse/delta rows are left out)."""
    hq, hkv = c["num_attention_heads"], c["num_key_value_heads"]
    per_layer = (6 * hq + 6 * hkv) * seq * c["head_dim"] * dtype_bytes
    return float(batch * c["num_hidden_layers"] * per_layer)


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one decode step at the batch in flight at the
    middle of the traced span (weights once + each sequence's keys and
    values once, against HBM bandwidth; or the FLOPs against the MXU,
    whichever is larger); None where the run does not say the batch."""
    from . import readers   # what the run observed

    span = obs.get("trace_span")
    if not span or span[0] is None:
        return None
    sequences, positions = readers.context_in_flight(
        obs, (span[0] + span[1]) / 2)
    if not sequences:
        return None
    cfg, peaks = obs["cell"].config, obs["peaks"]
    return max(
        decode_step_bytes(cfg, positions) / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, sequences, positions)
        / peaks["bf16_flops_per_s"])

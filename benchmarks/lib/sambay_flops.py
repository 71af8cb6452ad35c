"""Parameters, operations and bytes the ALGORITHM of a decoder-hybrid-decoder
(SambaY: Phi-4-mini-flash-reasoning) needs, from shapes alone.  The
yardstick's own arithmetic: nothing here is read from the program.

A configuration is the dict of ``benchmarks/configs/<name>.json`` (the
published ``config.json`` keys and the sizes it lists as assumed:
``mamba_d_state``, ``mamba_d_conv``, ``mamba_expand``, ``mamba_dt_rank``).
A multiply-add counts as 2 FLOPs.  Every layer has the dense SwiGLU MLP
and two LayerNorms with a bias.

By hand, phi-4-mini-flash-reasoning (hidden 2,560, 40 / 20 heads of 64,
MLP 10,240, 5,120 Mamba channels x 16 states, dt rank 160):

    MLP                  3 x 2,560 x 10,240                    78,643,200
    two LayerNorms       2 x 2 x 2,560                             10,240
    Mamba-1 mixer        2,560 x 10,240 + (4 + 1) x 5,120
                         + 5,120 x 192 + 160 x 5,120 + 5,120
                         + 16 x 5,120 + 5,120 + 5,120 x 2,560  41,241,600
    window / full attn   2,560 x 5,120 + 5,120 + 2,560 x 2,560
                         + 2,560 + 4 x 64 + 128                19,668,864
    cross attention      2 x (2,560 x 2,560 + 2,560) + 384     13,112,704
    gated memory unit    2 x 2,560 x 5,120                     26,214,400
    9 + 8 + 1 + 7 + 7 layers                                3,340,393,984
    embedding (tied) 200,064 x 2,560 = 512,163,840; final LayerNorm 5,120
    in all                                                  3,852,562,944
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def layer_counts(c: Dict[str, Any]) -> Dict[str, int]:
    """Layers of each kind, from the published keys: Mamba-1 on the even
    layers up to L/2, window attention on the odd ones below it, ONE full
    attention layer at L/2 + 1, then gated memory units (even) and cross
    attention (odd)."""
    L = c["num_hidden_layers"]
    half = L // 2
    return {"mamba": half // 2 + 1, "window": half // 2, "attention": 1,
            "gmu": (L - half - 2) // 2, "cross": (L - half - 2) // 2}


def dims(c: Dict[str, Any]):
    """(hidden, q width, kv width, Mamba channels, states, dt rank)."""
    h = c["hidden_size"]
    d = c.get("head_dim") or h // c["num_attention_heads"]
    return (h, c["num_attention_heads"] * d, c["num_key_value_heads"] * d,
            c["mamba_expand"] * h, c["mamba_d_state"], c["mamba_dt_rank"])


def mixer_matmul_params(c: Dict[str, Any]) -> Dict[str, int]:
    """The matmul weights of ONE mixer of each kind."""
    h, q, kv, di, n, r = dims(c)
    return {"mamba": h * 2 * di + di * (r + 2 * n) + r * di + di * h,
            "window": h * (q + 2 * kv) + q * h,
            "attention": h * (q + 2 * kv) + q * h,
            "cross": h * q + q * h,
            "gmu": 2 * h * di}


def mixer_small_params(c: Dict[str, Any]) -> Dict[str, int]:
    """What no matmul owns of ONE mixer: biases, conv, A_log, D, the
    lambda vectors and the sub-norm."""
    h, q, kv, di, n, _r = dims(c)
    d = q // c["num_attention_heads"]
    diff = 4 * d + 2 * d
    return {"mamba": (c["mamba_d_conv"] + 1) * di + di + n * di + di,
            "window": q + 2 * kv + h + diff,
            "attention": q + 2 * kv + h + diff,
            "cross": q + h + diff, "gmu": 0}


def matmul_params(c: Dict[str, Any], kinds: Sequence[str] = ()) -> int:
    """Weights a token is multiplied by in one forward pass over the layers
    of ``kinds`` (all of them): each layer's mixer and MLP, and -- over all
    layers -- the output head (the tied embedding read as a matrix)."""
    counts, per = layer_counts(c), mixer_matmul_params(c)
    mlp = 3 * c["hidden_size"] * c["intermediate_size"]
    kinds = kinds or tuple(counts)
    head = c["hidden_size"] * c["vocab_size"] if len(kinds) == len(counts) \
        else 0
    return sum(counts[k] * (per[k] + mlp) for k in kinds) + head


def parameters(c: Dict[str, Any]) -> int:
    """Every parameter (the table in the module docstring)."""
    counts, small = layer_counts(c), mixer_small_params(c)
    h = c["hidden_size"]
    return (matmul_params(c) + sum(counts[k] * small[k] for k in counts)
            + sum(counts.values()) * 4 * h + 2 * h)


def kv_row_bytes(c: Dict[str, Any]) -> int:
    """K and V of ONE position of ONE layer, as stored."""
    return 2 * dims(c)[2] * _ITEMSIZE[c["dtype"]["serve"]]


def slot_bytes(c: Dict[str, Any], max_len: int) -> Dict[str, int]:
    """Bytes ONE slot holds, by pool: ``kv_full`` (the K/V layer's rows,
    every position), ``kv_window`` (a ring a window layer), ``ssm`` and
    ``conv`` (a Mamba layer's state and last d_conv - 1 inputs)."""
    counts = layer_counts(c)
    _h, _q, _kv, di, n, _r = dims(c)
    return {
        "kv_full": counts["attention"] * max_len * kv_row_bytes(c),
        "kv_window": counts["window"] * min(c["sliding_window"], max_len)
        * kv_row_bytes(c),
        "ssm": counts["mamba"] * di * n * _ITEMSIZE[c["dtype"]["ssm_state"]],
        "conv": counts["mamba"] * (c["mamba_d_conv"] - 1) * di
        * _ITEMSIZE[c["dtype"]["serve"]]}


def shared_kv_bytes(c: Dict[str, Any], lengths: Sequence[float]) -> float:
    """Least HBM traffic of one step's reads of the ONE full-length pool:
    each live row's keys and values once a READING layer (the K/V layer
    and every cross layer), whatever implements the reads."""
    counts = layer_counts(c)
    return (counts["attention"] + counts["cross"]) * kv_row_bytes(c) \
        * float(sum(lengths))


def window_kv_bytes(c: Dict[str, Any], lengths: Sequence[float]) -> float:
    return layer_counts(c)["window"] * kv_row_bytes(c) * float(
        sum(min(n, c["sliding_window"]) for n in lengths))


def attention_flops(c: Dict[str, Any], lengths: Sequence[float]) -> float:
    """QK^T and PV of one step: a query head's 64-wide score and its
    pair's 128-wide value a key, over every attending layer."""
    counts = layer_counts(c)
    d = dims(c)[1] // c["num_attention_heads"]
    per_key = 2 * c["num_attention_heads"] * (d + 2 * d)
    full = (counts["attention"] + counts["cross"]) * float(sum(lengths))
    ring = counts["window"] * float(
        sum(min(n, c["sliding_window"]) for n in lengths))
    return per_key * (full + ring)


def state_update_flops(c: Dict[str, Any], rows: float) -> float:
    """Per state element and row: dt x A, the exponential, decay x S,
    (dt u) x B, their sum, and the contraction with C (2): 7."""
    _h, _q, _kv, di, n, _r = dims(c)
    return 7.0 * rows * layer_counts(c)["mamba"] * di * n


def decode_step_bytes(c: Dict[str, Any], lengths: Sequence[float]) -> float:
    """Least HBM traffic of ONE decode step: every matmul weight once, the
    shared pool's live rows once a reading layer, the rings' live rows,
    and the states of the rows it advances read and written once."""
    slot = slot_bytes(c, 1)
    return (matmul_params(c) * _ITEMSIZE[c["dtype"]["serve"]]
            + shared_kv_bytes(c, lengths) + window_kv_bytes(c, lengths)
            + 2.0 * len(lengths) * (slot["ssm"] + slot["conv"]))


def decode_step_flops(c: Dict[str, Any], lengths: Sequence[float]) -> float:
    return (2.0 * matmul_params(c) * len(lengths)
            + attention_flops(c, lengths)
            + state_update_flops(c, len(lengths)))


def prefill_flops(c: Dict[str, Any], prompt: float, skip: bool = True):
    """Matmul FLOPs of ONE prompt's prefill (attention's own left out):
    the self-decoder and the K/V layer's key and value projections over
    every position; with ``skip`` the rest at one position, without it at
    every position."""
    counts = layer_counts(c)
    h, _q, kv, *_ = dims(c)
    self_decoder = matmul_params(c, ("mamba", "window")) + 2 * h * kv
    rest = matmul_params(c) - self_decoder
    return 2.0 * (self_decoder * prompt + rest * (1 if skip else prompt))


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one WHOLE decode step (every weight once, the
    shared pool's live rows once a reading layer, the rings' live rows,
    the states of the rows it advances read and written: HBM bytes or
    FLOPs at peak, the larger); None where the run does not say the rows
    in flight."""
    from . import swa_names   # what the run observed

    lengths = swa_names._traced_lengths(obs)
    if lengths is None:
        return None
    cfg, peaks = obs["cell"].config, obs["peaks"]
    return max(
        decode_step_bytes(cfg, lengths) / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lengths) / peaks["bf16_flops_per_s"])

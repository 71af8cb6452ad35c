"""Parameters, operations and bytes the ALGORITHM of a hybrid of KDA (gated
delta-rule linear attention) and gated softmax-attention layers with a
shared and routed experts needs, from shapes alone (Solar-Open2).  The
yardstick's own arithmetic: nothing here is read from the program.

A configuration is the dict of ``benchmarks/configs/<name>.json``: the
published ``config.json`` keys (``gqa_layers``, ``linear_attn_config``,
``moe_intermediate_size``, ``n_shared_experts``), the share this chip holds
(``n_routed_experts`` HELD here, the published count under ``share``) and
the sizes it lists as assumed (``kda_gate_rank``).  A multiply-add counts as
2 FLOPs.

By hand, solar-open2-250b as cut (stream 4,096; 64 / 8 heads of 128; KDA 64
heads of 128, conv 4, gates of rank 128; experts of 1,280: 40 of 320 held
beside one shared; 4 layers = 1 attention + 3 KDA; 24,576 vocabulary rows):

    KDA mixer        3 x 4,096 x 8,192 + 8,192 x 4,096
                     + 2 x (4,096 x 128 + 128 x 8,192) + 4,096 x 64
                     + 3 x 8,192 x 4 + 64 + 8,192 + 128        137,732,288
    attention mixer  4,096 x (8,192 + 2 x 1,024) + 8,192 x 4,096
                     + 4,096 x 8,192 (the gate)                109,051,904
    FFN, every layer 4,096 x 320 + 320 (router, bias)
                     + 3 x 4,096 x 1,280 (shared)
                     + 40 x 3 x 4,096 x 1,280 (held)           646,185,280
    two norms        2 x 4,096                                       8,192
    a KDA layer 783,925,760; an attention layer 755,245,376
    1 + 3 layers                                             3,107,022,656
    embedding + untied head 2 x 24,576 x 4,096 = 201,326,592; final norm
    in all                                                   3,308,353,344
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from .moe_flops import expert_params   # one expert's three matrices

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def layer_counts(c: Dict[str, Any]) -> Dict[str, int]:
    """Layers of each kind among the ``num_hidden_layers`` here."""
    L = c["num_hidden_layers"]
    attending = sum(1 for i in c["gqa_layers"] if i < L)
    return {"attention": attending, "kda": L - attending}


def dims(c: Dict[str, Any]):
    """(stream, q width, kv width, KDA heads, KDA head, gate rank, taps)."""
    lin = c["linear_attn_config"]
    d = c["head_dim"]
    return (c["hidden_size"], c["num_attention_heads"] * d,
            c["num_key_value_heads"] * d, lin["num_heads"], lin["head_dim"],
            c["kda_gate_rank"], lin["short_conv_kernel_size"])


def mixer_matmul_params(c: Dict[str, Any]) -> Dict[str, int]:
    """The matmul weights of ONE mixer of each kind."""
    h, q, kv, H, d, r, _k = dims(c)
    hd = H * d
    return {"kda": h * 3 * hd + hd * h + 2 * (h * r + r * hd) + h * H,
            "attention": h * (q + 2 * kv) + q * h + h * q}


def mixer_small_params(c: Dict[str, Any]) -> Dict[str, int]:
    """What no matmul owns of ONE mixer: the conv's taps, ``A_log``,
    ``dt_bias`` and the head norm."""
    _h, _q, _kv, H, d, _r, k = dims(c)
    return {"kda": 3 * H * d * k + H + H * d + d, "attention": 0}


def ffn_dense_params(c: Dict[str, Any]) -> int:
    """What every token is multiplied by in a layer's FFN whatever it is
    routed to: the router (all published experts) and the shared expert."""
    routed = c["share"]["n_routed_experts_published"]
    return c["hidden_size"] * routed + c["n_shared_experts"] * expert_params(c)


def parameters(c: Dict[str, Any]) -> int:
    """Every parameter held here (the table in the module docstring)."""
    counts = layer_counts(c)
    per, small = mixer_matmul_params(c), mixer_small_params(c)
    h, L = c["hidden_size"], c["num_hidden_layers"]
    routed = c["share"]["n_routed_experts_published"]
    ffn = ffn_dense_params(c) + routed \
        + c["n_routed_experts"] * expert_params(c)
    head = (1 if c["tie_word_embeddings"] else 2) * c["vocab_size"] * h
    return (sum(counts[k] * (per[k] + small[k]) for k in counts)
            + L * (ffn + 2 * h) + head + h)


def dense_matmul_params(c: Dict[str, Any]) -> int:
    """Matmul weights EVERY token of a step meets: the mixers, routers and
    shared experts of all layers, and the head."""
    counts, per = layer_counts(c), mixer_matmul_params(c)
    return (sum(counts[k] * per[k] for k in counts)
            + c["num_hidden_layers"] * ffn_dense_params(c)
            + c["hidden_size"] * c["vocab_size"])


def kv_row_bytes(c: Dict[str, Any]) -> int:
    """K and V of ONE position of ONE attention layer, as stored."""
    return 2 * dims(c)[2] * _ITEMSIZE[c["dtype"]["serve"]]


def state_bytes(c: Dict[str, Any]) -> int:
    """ONE slot's matrix state of ONE KDA layer: heads x d x d."""
    _h, _q, _kv, H, d, _r, _k = dims(c)
    return H * d * d * _ITEMSIZE[c["dtype"]["kda_state"]]


def slot_bytes(c: Dict[str, Any], max_len: int) -> Dict[str, int]:
    """Bytes ONE slot holds, by pool: ``kv`` (the attention layers' rows,
    every position), ``ssm`` (a KDA layer's matrix state) and ``conv`` (its
    last taps - 1 inputs of the q, k and v convolutions)."""
    counts = layer_counts(c)
    _h, _q, _kv, H, d, _r, k = dims(c)
    return {"kv": counts["attention"] * max_len * kv_row_bytes(c),
            "ssm": counts["kda"] * state_bytes(c),
            "conv": counts["kda"] * (k - 1) * 3 * H * d
            * _ITEMSIZE[c["dtype"]["serve"]]}


def state_update_bytes(c: Dict[str, Any], rows: float) -> float:
    """Least HBM traffic of the delta rule's update of ``rows`` (slot,
    step) pairs: each advanced slot's state once in and once out a KDA
    layer, whatever implements it."""
    return 2.0 * rows * layer_counts(c)["kda"] * state_bytes(c)


def state_update_flops(c: Dict[str, Any], rows: float) -> float:
    """Per state element and row: the decay, k x S' and its sum (2), the
    correction k x d and its sum (2), q x S'' and its sum (2): 7."""
    _h, _q, _kv, H, d, _r, _k = dims(c)
    return 7.0 * rows * layer_counts(c)["kda"] * H * d * d


def attention_flops(c: Dict[str, Any], lengths: Sequence[float]) -> float:
    """QK^T and PV of one step over the attention layers."""
    per_key = 2 * 2 * c["num_attention_heads"] * c["head_dim"]
    return layer_counts(c)["attention"] * per_key * float(sum(lengths))


def decode_step_bytes(c: Dict[str, Any], lengths: Sequence[float],
                      experts_touched: float) -> float:
    """Least HBM traffic of ONE decode step: every dense matmul weight
    once, the three matrices of each (layer, expert) pair touched, the
    attention layers' live rows once, and the states of the rows it
    advances read and written once."""
    item = _ITEMSIZE[c["dtype"]["serve"]]
    slot = slot_bytes(c, 1)
    return ((dense_matmul_params(c) + experts_touched * expert_params(c))
            * item
            + layer_counts(c)["attention"] * kv_row_bytes(c)
            * float(sum(lengths))
            + 2.0 * len(lengths) * (slot["ssm"] + slot["conv"]))


def decode_step_flops(c: Dict[str, Any], lengths: Sequence[float],
                      expert_rows: float) -> float:
    return (2.0 * dense_matmul_params(c) * len(lengths)
            + 2.0 * expert_rows * expert_params(c)
            + attention_flops(c, lengths)
            + state_update_flops(c, len(lengths)))


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one WHOLE decode step at the rows in flight at the
    middle of the traced span and the experts its steps touched (HBM bytes
    or FLOPs at peak, the larger); None where the run says neither."""
    from . import moe_names, swa_names   # what the run observed

    lengths = swa_names._traced_lengths(obs)
    medians = moe_names.chunk_medians(obs)
    if lengths is None or medians is None:
        return None
    rows, touched, _ = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    return max(
        decode_step_bytes(cfg, lengths, touched) / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lengths, rows) / peaks["bf16_flops_per_s"])

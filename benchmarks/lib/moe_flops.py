"""Operations and bytes the ALGORITHM of a decoder with experts needs,
from shapes alone (``lib/flops.py`` counts a dense decoder only).  The
yardstick's own arithmetic: nothing here is read from the program.

A configuration is the dict of ``benchmarks/configs/<name>.json`` (the
published ``config.json`` keys: ``num_experts``, ``num_experts_per_tok``,
``intermediate_size`` = the width of ONE expert).  A multiply-add counts
as 2 FLOPs.  The whole-model and whole-step counts are of a model whose
every layer has experts and none is shared (OLMoE).

The grouped matmuls' own counts (``expert_params``,
``expert_matmul_bytes``, ``expert_matmul_flops``) and the three facts
about the experts (``expert_width``, ``expert_layers``,
``experts_held``) are every configuration's with experts: they read the
keys that say them whatever the family -- ``moe_intermediate_size``
where a configuration has a dense width beside its experts', and what
the program is given under ``program_fields`` (``first_dense_layers``,
``moe_experts``, ``moe_held``).  An expert that is not three matrices of
stream x width, or expert layers that are not all but the leading dense
ones, the file states under ``expert_shape``: ``{"matrices": 2,
"row_width": 1024, "layers": 5}`` (any of the three; what is absent is
counted as above) -- two matrices an expert, a row that enters and leaves
1,024 wide (a latent), five layers with experts among those held.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from .flops import kv_bytes_per_token  # K and V rows of a position


def _sizes(c: Dict[str, Any]):
    h = c["hidden_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    return h, q, kv, c["intermediate_size"], c["vocab_size"], \
        c["num_experts"]


def expert_width(c: Dict[str, Any]) -> int:
    return c.get("moe_intermediate_size", c["intermediate_size"])


def _stated(c: Dict[str, Any], key: str, otherwise):
    return (c.get("expert_shape") or {}).get(key, otherwise)


def expert_layers(c: Dict[str, Any]) -> int:
    """Layers that have experts: all but the leading dense ones, or what
    the file states."""
    return _stated(c, "layers", c["num_hidden_layers"]
                   - c.get("program_fields", {}).get("first_dense_layers", 0))


def expert_matrices(c: Dict[str, Any]) -> int:
    """Matrices of one expert: gate, up and down, or what the file
    states (two where an expert has no gate)."""
    return _stated(c, "matrices", 3)


def expert_row_width(c: Dict[str, Any]) -> int:
    """The width a row enters an expert and leaves it with: the stream's,
    or what the file states (a latent's)."""
    return _stated(c, "row_width", c["hidden_size"])


def experts_held(c: Dict[str, Any]) -> int:
    """Experts of a layer this chip computes: the program's ``moe_held``
    range where it holds a share of them, else all."""
    fields = c["program_fields"]
    held = fields.get("moe_held")
    return held[1] - held[0] if held else fields["moe_experts"]


def expert_params(c: Dict[str, Any]) -> int:
    """The matrices of ONE expert of one layer."""
    return expert_matrices(c) * expert_row_width(c) * expert_width(c)


def dense_matmul_params_per_layer(c: Dict[str, Any]) -> int:
    """What every token is multiplied by in a layer whatever it is
    routed to: q, k, v, o and the router."""
    h, q, kv, _f, _v, e = _sizes(c)
    return h * q + 2 * h * kv + q * h + h * e


def param_count(c: Dict[str, Any], layers: int = None) -> int:
    """Every parameter, at ``layers`` layers (the file's own depth if
    not given): projections, the q and k norms' weights, the two block
    norms, router, experts; embedding, untied head, final norm."""
    h, q, kv, _f, v, e = _sizes(c)
    layers = c["num_hidden_layers"] if layers is None else layers
    per_layer = (dense_matmul_params_per_layer(c) + q + kv + 2 * h
                 + e * expert_params(c))
    head = 0 if c["tie_word_embeddings"] else v * h
    return layers * per_layer + v * h + head + h


def active_params(c: Dict[str, Any], layers: int = None) -> int:
    """Matmul weights ONE token meets in a forward pass: the dense part
    of every layer, its ``num_experts_per_tok`` experts, the head."""
    h, _q, _kv, _f, v, _e = _sizes(c)
    layers = c["num_hidden_layers"] if layers is None else layers
    return layers * (dense_matmul_params_per_layer(c)
                     + c["num_experts_per_tok"] * expert_params(c)) + h * v


def expert_matmul_bytes(c: Dict[str, Any], experts_touched: float,
                        expert_rows: float, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of the grouped matmuls alone: the matrices of
    each (layer, expert) pair that has a row, once, and each row's
    activations, a read or a write of it at each end of every matrix (of
    three: in at width h twice, the hidden row of width f out twice and
    in once, out at width h once)."""
    h, f = expert_row_width(c), expert_width(c)
    return (experts_touched * expert_params(c)
            + expert_rows * (expert_matrices(c) * (h + f))) * dtype_bytes


def expert_matmul_flops(c: Dict[str, Any], expert_rows: float) -> float:
    """``expert_rows`` (token, expert) assignments through an expert's
    matrices of h x f."""
    return 2.0 * expert_rows * expert_params(c)


def expert_matmul_train_flops(c: Dict[str, Any], rows: float) -> float:
    """The grouped matmuls of one TRAIN step over the ``rows`` the held
    experts computed in all expert layers together (what the router sent
    here: under random weights far from an even share, PERF.md section 6,
    PR 57): the forward's, and for each matrix its two backward products
    (the rows' and the weights' cotangents) = 3 x forward.  No
    recomputation."""
    return 3.0 * expert_matmul_flops(c, rows)


def expert_matmul_train_bytes(c: Dict[str, Any], rows: float,
                              dtype_bytes: int = 2) -> float:
    """Their least HBM traffic: the held experts' matrices read once
    forward and once backward, their float32 gradient written once, and
    the rows in and out of each pass at the width they enter with."""
    weights = expert_layers(c) * experts_held(c) * expert_params(c)
    return float(weights * (2 * dtype_bytes + 4)
                 + 4 * rows * expert_row_width(c) * dtype_bytes)


def decode_step_bytes(c: Dict[str, Any], experts_touched: float,
                      context_tokens: float, dtype_bytes: int = 2) -> float:
    """Least HBM traffic of ONE decode step: attention, router and head
    weights once (the embedding is gathered row-wise), the three matrices
    of each (layer, expert) pair TOUCHED in the step (``experts_touched``
    <= layers x experts), and each sequence's keys and values once
    (``context_tokens`` positions held by the batch in flight)."""
    h, _q, _kv, _f, v, _e = _sizes(c)
    dense = c["num_hidden_layers"] * dense_matmul_params_per_layer(c) \
        + h * v
    return (dense + experts_touched * expert_params(c)) * dtype_bytes \
        + context_tokens * kv_bytes_per_token(c, dtype_bytes)


def decode_step_flops(c: Dict[str, Any], batch: float,
                      context_tokens: float, expert_rows: float) -> float:
    """One decode step over ``batch`` sequences holding
    ``context_tokens`` positions in all, whose tokens made
    ``expert_rows`` (token, expert) assignments over all layers."""
    h, _q, _kv, _f, v, _e = _sizes(c)
    heads, d = c["num_attention_heads"], c["head_dim"]
    dense = c["num_hidden_layers"] * dense_matmul_params_per_layer(c) \
        + h * v
    attn = 2 * 2 * context_tokens * heads * d * c["num_hidden_layers"]
    return 2.0 * dense * batch + attn \
        + expert_matmul_flops(c, expert_rows)


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one decode step at the batch in flight at the
    middle of the traced span and the experts its steps touched (HBM
    bytes or FLOPs at peak, whichever is larger); None where the run
    says neither."""
    from . import moe_names, readers   # what the run observed

    span = obs.get("trace_span")
    medians = moe_names.chunk_medians(obs)
    if medians is None or not span or span[0] is None:
        return None
    sequences, positions = readers.context_in_flight(
        obs, (span[0] + span[1]) / 2)
    if not sequences:
        return None
    rows, touched, _ = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    return max(
        decode_step_bytes(cfg, touched, positions)
        / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, sequences, positions, rows)
        / peaks["bf16_flops_per_s"])

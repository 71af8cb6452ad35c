"""Parameters, operations and bytes the ALGORITHM of a Nemotron-H stack needs
(blocks of ONE sub-layer each: a Mamba-2 mixer with groups, softmax
attention, or a LatentMoE feed-forward part), from shapes alone.  The
yardstick's own arithmetic: nothing here is read from the program.  This
file counts the stack's parameters, its pools and its WHOLE decode step;
what the grouped matmuls and the state update alone must do is
``lib/moe_flops.py``'s and ``lib/ssm_flops.py``'s, every configuration's
with experts or a state-space mixer, at the shapes the file states under
``expert_shape`` (two matrices an expert, rows 1,024 wide, five expert
blocks) and ``ssm_shape`` (the mixer's, five Mamba blocks).

A configuration is the dict of ``benchmarks/configs/<name>.json``: the
published ``config.json`` keys (``hybrid_override_pattern``,
``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``,
``conv_kernel``, ``moe_latent_size``, ``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``) and the share this chip holds
(``n_routed_experts`` HELD here, the published count under ``share``).  A
multiply-add counts as 2 FLOPs.

By hand, nemotron-3-super-120b-a12b as cut (stream 4,096; Mamba-2 128 heads
of 64, 8 groups of 128 state dimensions, conv 4; GQA 32 / 2 heads of 128;
experts of 2 x 1,024 x 2,688 in a 1,024-wide latent: 128 of 512 held beside
one shared expert of 5,376 at full width; 11 blocks ``MEMEMEM*EME`` = 5 M +
1 * + 5 E; 32,768 vocabulary rows):

    M  W_in 4,096 x (8,192 + 10,240 + 128)                     76,021,760
       conv 4 x 10,240 + 10,240; dt_bias, A_log, D 3 x 128;
       gated norm 8,192                                            59,776
       W_out 8,192 x 4,096                                     33,554,432
       the block's norm                                             4,096
                                                              109,640,064
    *  4,096 x (4,096 + 2 x 256) + 4,096 x 4,096 + 4,096       35,655,680
    E  router 4,096 x 512 + 512; W_1, W_2 2 x 4,096 x 1,024;
       shared 2 x 4,096 x 5,376; the block's norm              54,530,560
       128 held x 2 x 1,024 x 2,688 (5,505,024 an expert)     704,643,072
                                                              759,173,632
    5 M + 1 * + 5 E                                         4,379,724,160
    embedding + untied head 2 x 32,768 x 4,096 = 268,435,456; final norm
    in all                                                  4,648,163,712
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from . import moe_flops, ssm_flops

_ITEMSIZE = {"bfloat16": 2, "float32": 4}


def block_counts(c: Dict[str, Any]) -> Dict[str, int]:
    """Blocks of each kind among the ``num_hidden_layers`` here."""
    pattern = c["hybrid_override_pattern"]
    return {kind: pattern.count(kind) for kind in "M*E"}


def mamba_dims(c: Dict[str, Any]):
    """(heads, head, state, groups, d_inner, conv_dim, taps)."""
    nh, hd = c["mamba_num_heads"], c["mamba_head_dim"]
    n, g = c["ssm_state_size"], c["n_groups"]
    return nh, hd, n, g, nh * hd, nh * hd + 2 * g * n, c["conv_kernel"]


def expert_params(c: Dict[str, Any]) -> int:
    """The TWO matrices of ONE routed expert, in the latent."""
    return 2 * c["moe_latent_size"] * c["moe_intermediate_size"]


def block_matmul_params(c: Dict[str, Any]) -> Dict[str, int]:
    """The matmul weights EVERY token meets in one block of each kind
    (an E block's: router, the two latent projections, the shared
    expert; its routed experts are counted by what a step touches)."""
    h = c["hidden_size"]
    nh, _hd, _n, _g, d_inner, conv_dim, _k = mamba_dims(c)
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    routed = c["share"]["n_routed_experts_published"]
    return {"M": h * (d_inner + conv_dim + nh) + d_inner * h,
            "*": h * (q + 2 * kv) + q * h,
            "E": h * routed + 2 * h * c["moe_latent_size"]
            + 2 * h * c["moe_shared_expert_intermediate_size"]}


def block_small_params(c: Dict[str, Any]) -> Dict[str, int]:
    """What no matmul owns of one block: its pre-norm and, of a mixer, the
    conv's taps and bias, ``dt_bias``, ``A_log``, ``D`` and the gated norm;
    of an E block the router's selection bias."""
    h = c["hidden_size"]
    nh, _hd, _n, _g, d_inner, conv_dim, k = mamba_dims(c)
    return {"M": h + (k + 1) * conv_dim + 3 * nh + d_inner, "*": h,
            "E": h + c["share"]["n_routed_experts_published"]}


def parameters(c: Dict[str, Any]) -> int:
    """Every parameter held here (the table in the module docstring)."""
    counts = block_counts(c)
    per, small = block_matmul_params(c), block_small_params(c)
    h = c["hidden_size"]
    head = (1 if c["tie_word_embeddings"] else 2) * c["vocab_size"] * h
    return (sum(counts[k] * (per[k] + small[k]) for k in counts)
            + counts["E"] * c["n_routed_experts"] * expert_params(c)
            + head + h)


def dense_matmul_params(c: Dict[str, Any]) -> int:
    """Matmul weights EVERY token of a step meets: all blocks' and the
    head."""
    counts, per = block_counts(c), block_matmul_params(c)
    return sum(counts[k] * per[k] for k in counts) \
        + c["hidden_size"] * c["vocab_size"]


def kv_row_bytes(c: Dict[str, Any]) -> int:
    """K and V of ONE position of ONE attention block, as stored."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] \
        * _ITEMSIZE[c["dtype"]["serve"]]


def state_bytes(c: Dict[str, Any]) -> int:
    """ONE slot's recurrent state of ONE Mamba block: heads x head x
    state."""
    nh, hd, n, _g, _d, _conv, _k = mamba_dims(c)
    return nh * hd * n * _ITEMSIZE[c["dtype"]["ssm_state"]]


def slot_bytes(c: Dict[str, Any], max_len: int) -> Dict[str, int]:
    """Bytes ONE slot holds, by pool: ``kv`` (the attention blocks' rows,
    every position), ``ssm`` (the Mamba blocks' recurrent states) and
    ``conv`` (their last taps - 1 inputs of the conv)."""
    counts = block_counts(c)
    _nh, _hd, _n, _g, _d, conv_dim, k = mamba_dims(c)
    return {"kv": counts["*"] * max_len * kv_row_bytes(c),
            "ssm": counts["M"] * state_bytes(c),
            "conv": counts["M"] * (k - 1) * conv_dim
            * _ITEMSIZE[c["dtype"]["serve"]]}


# ------------------------------------------------------- the whole step
def attention_flops(c: Dict[str, Any], lengths: Sequence[float]) -> float:
    """QK^T and PV of one step over the attention blocks."""
    per_key = 2 * 2 * c["num_attention_heads"] * c["head_dim"]
    return block_counts(c)["*"] * per_key * float(sum(lengths))


def decode_step_bytes(c: Dict[str, Any], lengths: Sequence[float],
                      experts_touched: float) -> float:
    """Least HBM traffic of ONE decode step: every dense matmul weight
    once, the two matrices of each (block, expert) pair touched, the
    attention blocks' live rows once, and the states of the rows it
    advances read and written once."""
    item = _ITEMSIZE[c["dtype"]["serve"]]
    slot = slot_bytes(c, 1)
    return ((dense_matmul_params(c) + experts_touched * expert_params(c))
            * item
            + block_counts(c)["*"] * kv_row_bytes(c) * float(sum(lengths))
            + 2.0 * len(lengths) * (slot["ssm"] + slot["conv"]))


def decode_step_flops(c: Dict[str, Any], lengths: Sequence[float],
                      expert_rows: float) -> float:
    return (2.0 * dense_matmul_params(c) * len(lengths)
            + moe_flops.expert_matmul_flops(c, expert_rows)
            + attention_flops(c, lengths)
            + ssm_flops.state_update_flops(c, len(lengths)))


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one WHOLE decode step at the rows in flight at the
    middle of the traced span and the experts its steps touched (HBM bytes
    or FLOPs at peak, the larger); None where the run says neither."""
    from . import moe_names, swa_names   # what the run observed

    lengths = swa_names._traced_lengths(obs)
    load = moe_names.chunk_medians(obs)
    if lengths is None or load is None:
        return None
    rows, touched, _ = load
    cfg, peaks = obs["cell"].config, obs["peaks"]
    return max(
        decode_step_bytes(cfg, lengths, touched) / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lengths, rows) / peaks["bf16_flops_per_s"])

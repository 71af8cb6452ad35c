"""Parameters, operations and bytes the ALGORITHM of a DeepSeek-V2-shaped
decoder needs (latent attention, leading dense layers, shared experts
beside a held share of the routed ones), from shapes alone.  The
yardstick's own arithmetic: nothing here is read from the program.

A configuration is the dict of ``benchmarks/configs/<name>.json``
(published key names): ``n_routed_experts`` is the count HELD on this chip
and ``share.n_routed_experts_published`` the router's width;
``intermediate_size`` is a DENSE layer's width, ``moe_intermediate_size``
an expert's (which is why ``lib/moe_flops.py``'s whole-step counts, that
read the first as the second, are not this model's).  A multiply-add
counts as 2 FLOPs; bytes are ``dtype_bytes`` a value (bfloat16).
``decode_step_least_s`` is the floor the configuration's file names under
``roofline``.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, Optional


def latent_width(c: Dict[str, Any]) -> int:
    """Values a token and layer keeps: ``c_kv`` and ``k_rope`` (576)."""
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def attention_params(c: Dict[str, Any]) -> int:
    """One layer's latent attention: W_DQ, its norm, W_UQ, W_DKV, its
    norm, W_UKV, W_O."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    rq, r = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"])
    return (d * rq + rq + rq * h * (nope + rope) + d * (r + rope) + r
            + r * h * (nope + v) + h * v * d)


def swiglu_params(c: Dict[str, Any], width: int) -> int:
    return 3 * c["hidden_size"] * width


def expert_params(c: Dict[str, Any]) -> int:
    """One routed expert's three matrices."""
    return swiglu_params(c, c["moe_intermediate_size"])


def shared_width(c: Dict[str, Any]) -> int:
    return c["n_shared_experts"] * c["moe_intermediate_size"]


def router_width(c: Dict[str, Any]) -> int:
    return c.get("share", {}).get("n_routed_experts_published",
                                  c["n_routed_experts"])


def dense_layer_params(c: Dict[str, Any]) -> int:
    """A leading layer: attention, two block norms, a SwiGLU of
    ``intermediate_size``."""
    return attention_params(c) + 2 * c["hidden_size"] \
        + swiglu_params(c, c["intermediate_size"])


def expert_layer_params_outside_experts(c: Dict[str, Any]) -> int:
    """An expert layer without its routed experts: attention, two block
    norms, the router (all published outputs), the shared expert."""
    return attention_params(c) + 2 * c["hidden_size"] \
        + c["hidden_size"] * router_width(c) \
        + swiglu_params(c, shared_width(c))


def expert_layers(c: Dict[str, Any], layers: int = 0) -> int:
    return (layers or c["num_hidden_layers"]) - c["first_k_dense_replace"]


def parameters(c: Dict[str, Any], layers: int = 0, experts: int = 0,
               vocab: int = 0) -> int:
    """Every parameter at ``layers`` deep with ``experts`` routed experts
    a layer and ``vocab`` rows (each: the file's own where 0): embedding,
    untied head, final norm, the leading dense layers, the expert layers."""
    layers = layers or c["num_hidden_layers"]
    experts = experts or c["n_routed_experts"]
    vocab = vocab or c["vocab_size"]
    return (2 * vocab * c["hidden_size"] + c["hidden_size"]
            + c["first_k_dense_replace"] * dense_layer_params(c)
            + expert_layers(c, layers) * (
                expert_layer_params_outside_experts(c)
                + experts * expert_params(c)))


# ------------------------------------------------------------- attention
def latent_bytes_per_position(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """ONE layer's latent row of ONE position (1,152 bytes)."""
    return latent_width(c) * dtype_bytes


def decode_attention_flops_per_position(c: Dict[str, Any]) -> int:
    """ONE layer, ONE attended position, absorbed: every head's score
    (``latent_width`` wide) and its value (``kv_lora_rank`` wide):
    2 x 128 x (576 + 512) = 278,528."""
    return 2 * c["num_attention_heads"] * (latent_width(c)
                                           + c["kv_lora_rank"])


def decode_attention_bytes(c: Dict[str, Any], lengths: Iterable[float],
                           dtype_bytes: int = 2) -> float:
    """Least HBM traffic of a step's attention over all layers: each live
    row's latent rows once."""
    return c["num_hidden_layers"] * float(sum(lengths)) \
        * latent_bytes_per_position(c, dtype_bytes)


def decode_attention_flops(c: Dict[str, Any],
                           lengths: Iterable[float]) -> float:
    return c["num_hidden_layers"] * float(sum(lengths)) \
        * decode_attention_flops_per_position(c)


def prefill_attention_flops(c: Dict[str, Any], length: float,
                            heads: int = 0) -> float:
    """q.k (192 wide) and p.v (128 wide) of ONE prompt's expanded
    attention in ONE layer for ``heads`` heads (all of them where 0),
    inside the causal mask: what has to be computed, not what a tiled or
    padded kernel computes."""
    pairs = length * (length + 1) / 2.0
    return 2.0 * pairs * (heads or c["num_attention_heads"]) * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


# ----------------------------------------------------------- decode step
def step_matmul_params(c: Dict[str, Any]) -> int:
    """What every token of a step multiplies by, whatever it is routed
    to: the layers outside their routed experts (norms left in: a
    hundred-thousandth) and the head; the embedding is gathered row-wise."""
    return (c["first_k_dense_replace"] * dense_layer_params(c)
            + expert_layers(c) * expert_layer_params_outside_experts(c)
            + c["hidden_size"] * c["vocab_size"])


def decode_step_bytes(c: Dict[str, Any], experts_touched: float,
                      lengths: Iterable[float],
                      dtype_bytes: int = 2) -> float:
    """Least HBM traffic of ONE decode step: dense, shared and head
    weights once, the three matrices of each (layer, held expert) pair
    TOUCHED in the step, each live row's latent rows once."""
    return (step_matmul_params(c) + experts_touched * expert_params(c)) \
        * dtype_bytes + decode_attention_bytes(c, lengths, dtype_bytes)


def decode_step_flops(c: Dict[str, Any], lengths: Iterable[float],
                      expert_rows: float) -> float:
    lengths = list(lengths)
    return 2.0 * step_matmul_params(c) * len(lengths) \
        + decode_attention_flops(c, lengths) \
        + 2.0 * expert_rows * expert_params(c)


def decode_step_least_s(obs) -> Optional[float]:
    """Least seconds of one decode step (dense, shared and head weights
    once, three matrices of each (layer, held expert) touched, the latent
    rows in flight: HBM bytes or the step's FLOPs at peak, the larger);
    None where the run says neither."""
    from . import mla_names, swa_names   # what the run observed

    lengths = swa_names._traced_lengths(obs)
    medians = mla_names.chunk_medians(obs)
    if lengths is None or medians is None:
        return None
    rows, touched = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    return max(
        decode_step_bytes(cfg, touched, lengths) / peaks["hbm_bytes_per_s"],
        decode_step_flops(cfg, lengths, rows) / peaks["bf16_flops_per_s"])

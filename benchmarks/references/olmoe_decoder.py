"""Plain reference of OLMoE's decoder (OLMoE-1B-7B-0125-Instruct;
arXiv:2409.02060, transformers' ``modeling_olmoe`` as known): token
embedding; per layer RMSNorm -> q, k, v projections -> RMSNorm on q and
on k, each over its WHOLE projection (before the split into heads,
before RoPE) -> rotate-half RoPE -> causal multi-head attention ->
residual; RMSNorm -> router logits (no bias) -> softmax in float32 over
ALL experts -> the top ``num_experts_per_tok`` probabilities, used as
they are when ``norm_topk_prob`` is false (they do not sum to one) ->
sum of the chosen experts' SwiGLU under those gates -> residual; final
RMSNorm; untied output head.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no scan.
EVERY expert is computed on EVERY token and the result is masked by
top-k membership: no sort, no groups, no capacity, so it cannot share a
routing bug with ``ray_tpu/models/moe.py``.  It shares nothing with
``ray_tpu/models/`` but the parameter pytree's key names:

    embed_tokens (V, H); layers.{attn_norm (L, H), wq (L, H, Hq*D),
    wk, wv (L, H, Hkv*D), q_norm (L, Hq*D), k_norm (L, Hkv*D),
    wo (L, Hq*D, H), mlp_norm (L, H), router (L, H, E),
    w_gate, w_up (L, E, H, F), w_down (L, E, F, H)}; final_norm (H,);
    lm_head (H, V).

Departures from the published description: none in the mathematics.
``clip_qkv`` is null in the source and not modelled; a config that sets
it is refused.  The weights are whatever the caller passes (random, from
the seed).  One layer is one jitted call that takes the layer's weights
as stored and widens them to float32 inside, so a layer of 64 experts at
full width never holds more than its own float32 copy.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x (B, S, heads, D), positions 0..S-1, rotate-half convention:
    the first half of D is paired with the second."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d // 2, dtype=F32) / (d // 2))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, w, heads, kv_heads, head_dim, theta, eps, top_k, norm_topk):
    w = {k: v.astype(F32) for k, v in w.items()}
    b, s, _ = x.shape
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _rms_norm(h @ w["wq"], w["q_norm"], eps)
    k = _rms_norm(h @ w["wk"], w["k_norm"], eps)
    q = _rope(q.reshape(b, s, heads, head_dim), theta)
    k = _rope(k.reshape(b, s, kv_heads, head_dim), theta)
    v = (h @ w["wv"]).reshape(b, s, kv_heads, head_dim)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=2)   # query head i reads kv head i//group
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
    x = x + attn @ w["wo"]

    h = _rms_norm(x, w["mlp_norm"], eps)
    gates = jax.nn.softmax(h @ w["router"], axis=-1)          # (B, S, E)
    _, chosen = jax.lax.top_k(gates, top_k)
    member = jax.nn.one_hot(chosen, gates.shape[-1], dtype=F32).sum(-2)
    gates = gates * member              # every expert not chosen: zero
    if norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    # every expert on every token
    act = jax.nn.silu(jnp.einsum("bsh,ehf->bsef", h, w["w_gate"])) \
        * jnp.einsum("bsh,ehf->bsef", h, w["w_up"])
    out = jnp.einsum("bsef,efh->bseh", act, w["w_down"])
    return x + jnp.einsum("bseh,bse->bsh", out, gates)


# static: heads, kv_heads, head_dim, theta, eps, top_k, norm_topk
_layer_jit = jax.jit(_layer, static_argnums=(2, 3, 4, 5, 6, 7, 8))


def _embed(table, tokens):
    return table[tokens].astype(F32)


def _head(x, final_norm, head, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def _gap(lg, tokens):
    """Per position: the top logit minus the logit of the NEXT token."""
    nxt = jnp.roll(tokens, -1, axis=1)
    return lg.max(-1) - jnp.take_along_axis(lg, nxt[..., None], -1)[..., 0]


_embed_jit = jax.jit(_embed)
_head_jit = jax.jit(_head, static_argnums=(3,))
_gap_jit = jax.jit(_gap)


def _sizes(config: Dict[str, Any]):
    if config.get("clip_qkv") is not None:
        raise ValueError("olmoe_decoder: clip_qkv is not modelled")
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], float(config["rope_theta"]),
            float(config["rms_norm_eps"]), config["num_experts_per_tok"],
            bool(config["norm_topk_prob"]))


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any]):
    """(B, S, V) float32 logits for ``tokens`` (B, S) int32.  ``config``
    is the configuration file's dict (published key names)."""
    sizes = _sizes(config)
    with jax.default_matmul_precision("highest"):
        x = _embed_jit(params["embed_tokens"], tokens)
        for i in range(config["num_hidden_layers"]):
            x = _layer_jit(
                x, {k: v[i] for k, v in params["layers"].items()}, *sizes)
        head = (params["embed_tokens"].T if config["tie_word_embeddings"]
                else params["lm_head"])
        return _head_jit(x, params["final_norm"], head,
                         float(config["rms_norm_eps"]))


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """For a greedy decoder's ``emitted`` tokens after ``prompt``: at each
    emitted position, the reference's top logit minus the reference's
    logit of the token that was emitted (0 where they agree), one full
    forward pass over prompt + emitted.  ``pad_to`` lengthens the row
    with zeros to one compiled shape: causal attention keeps what follows
    a position from reaching it, and an expert layer mixes no positions."""
    seq = list(prompt) + list(emitted)
    seq = np.asarray(seq + [0] * max(0, pad_to - len(seq)), np.int32)[None]
    gap = np.asarray(_gap_jit(logits(params, jnp.asarray(seq), config),
                              seq))[0]
    return gap[len(prompt) - 1:len(prompt) - 1 + len(emitted)]

"""Plain reference of the dense pre-norm decoder both first
configurations are (InternLM2, arXiv:2403.17297 section 2; SmolLM2,
arXiv:2502.02737): token embedding; per layer RMSNorm -> grouped-query
causal attention with rotate-half RoPE -> residual, RMSNorm -> SwiGLU ->
residual; final RMSNorm; output head (tied to the embedding or its own).

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")`` (on a TPU an f32 matmul is
otherwise done in bf16 passes): no kernel, no cache, no scan, no
batching tricks.  It shares nothing with ``ray_tpu/models/llama.py``
but the parameter pytree's key names, which is how the program hands
over its weights:

    embed_tokens (V, H); layers.{attn_norm (L, H), wq (L, H, Hq*D),
    wk, wv (L, H, Hkv*D), wo (L, Hq*D, H), mlp_norm (L, H),
    w_gate, w_up (L, H, F), w_down (L, F, H)}; final_norm (H,);
    lm_head (H, V) unless tied.

Departures from the published models: none in the mathematics.  The
weights are whatever the caller passes (random, from the seed).  One
layer is one jitted call, forward and backward (``jax.vjp`` of the same
``_layer``), so a 24-layer model at full width never holds more than one
layer's float32 weights or attention scores.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict

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


def _layer(x, w, heads, kv_heads, head_dim, theta, eps):
    b, s, _ = x.shape
    h = _rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"]).reshape(b, s, heads, head_dim)
    k = (h @ w["wk"]).reshape(b, s, kv_heads, head_dim)
    v = (h @ w["wv"]).reshape(b, s, kv_heads, head_dim)
    q, k = _rope(q, theta), _rope(k, theta)
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
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def _layer_bwd(x, w, dy, *sizes):
    """(dx, dw): the layer is computed again here and pulled back, so no
    layer's scores outlive its own call."""
    _, pull = jax.vjp(lambda x, w: _layer(x, w, *sizes), x, w)
    return pull(dy)


# static: heads, kv_heads, head_dim, theta, eps
_layer_jit = jax.jit(_layer, static_argnums=(2, 3, 4, 5, 6))
_layer_bwd_jit = jax.jit(_layer_bwd, static_argnums=(3, 4, 5, 6, 7))


def _embed(table, tokens):
    return table[tokens].astype(F32)


def _embed_bwd(table, tokens, dx):
    return jnp.zeros(table.shape, F32).at[tokens].add(dx)


def _head(x, final_norm, head, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def _nll_sum(x, final_norm, head, tokens, eps):
    """Summed next-token cross-entropy over every position but the last
    of each row."""
    logp = jax.nn.log_softmax(_head(x, final_norm, head, eps)[:, :-1], -1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))


def _gap(lg, tokens):
    """Per position: the top logit minus the logit of the NEXT token."""
    nxt = jnp.roll(tokens, -1, axis=1)
    return lg.max(-1) - jnp.take_along_axis(lg, nxt[..., None], -1)[..., 0]


_embed_jit = jax.jit(_embed)
_embed_bwd_jit = jax.jit(_embed_bwd)
_head_jit = jax.jit(_head, static_argnums=(3,))
_nll_sum_grad_jit = jax.jit(jax.value_and_grad(_nll_sum, argnums=(0, 1, 2)),
                            static_argnums=(4,))
_gap_jit = jax.jit(_gap)


def _sizes(config: Dict[str, Any]):
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], float(config["rope_theta"]),
            float(config["rms_norm_eps"]))


def _layer_weights(params, i):
    return {k: v[i].astype(F32) for k, v in params["layers"].items()}


def _head_weights(params, config):
    return (params["embed_tokens"].T if config["tie_word_embeddings"]
            else params["lm_head"])


def _hidden(params, tokens, config):
    """The residual stream entering every layer, and leaving the last."""
    xs = [_embed_jit(params["embed_tokens"], tokens)]
    for i in range(config["num_hidden_layers"]):
        xs.append(_layer_jit(xs[-1], _layer_weights(params, i),
                             *_sizes(config)))
    return xs


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any]):
    """(B, S, V) float32 logits for ``tokens`` (B, S) int32.  ``config``
    is the configuration file's dict (published key names)."""
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, tokens, config)[-1]
        return _head_jit(x, params["final_norm"],
                         _head_weights(params, config),
                         float(config["rms_norm_eps"]))


def _laid_out_like(grads, weights):
    """Each gradient where its weight lives: a jitted call on sharded
    weights is free to hand its results back replicated."""
    return jax.tree.map(lambda g, w: jax.device_put(g, w.sharding),
                        grads, weights)


def loss_and_grads(params: Dict[str, Any], tokens, config: Dict[str, Any],
                   rows_at_a_time: int = 1, place: Callable = jnp.asarray):
    """Mean next-token cross-entropy of ``tokens`` (B, S) over every
    position but the last of each row, and its gradient in float32:
    ``{"embed_tokens", "layers": [one dict a layer], "final_norm",
    "lm_head" unless tied}``.  ``rows_at_a_time`` sequences go through at
    once, put on the device(s) by ``place``; the gradient is summed over
    the groups."""
    sizes, eps = _sizes(config), float(config["rms_norm_eps"])
    n_layers = config["num_hidden_layers"]
    total, count, grads = 0.0, 0, None
    with jax.default_matmul_precision("highest"):
        for i in range(0, tokens.shape[0], rows_at_a_time):
            rows = place(tokens[i:i + rows_at_a_time])
            xs = _hidden(params, rows, config)
            head = _head_weights(params, config)
            nll, (dx, d_norm, d_head) = _nll_sum_grad_jit(
                xs.pop(), params["final_norm"], head, rows, eps)
            d_layers = [None] * n_layers
            for j in reversed(range(n_layers)):
                w = _layer_weights(params, j)
                dx, dw = _layer_bwd_jit(xs.pop(), w, dx, *sizes)
                d_layers[j] = _laid_out_like(dw, w)
            group = _laid_out_like(
                {"embed_tokens": _embed_bwd_jit(params["embed_tokens"],
                                                rows, dx),
                 "lm_head": d_head},
                {"embed_tokens": params["embed_tokens"], "lm_head": head})
            group.update(layers=d_layers, final_norm=d_norm)
            grads = group if grads is None else jax.tree.map(
                jnp.add, grads, group)
            total += float(nll)
            count += rows.shape[0] * (rows.shape[1] - 1)
    if config["tie_word_embeddings"]:   # one matrix, reached both ways
        grads["embed_tokens"] = grads["embed_tokens"] \
            + grads.pop("lm_head").T
    return total / count, jax.tree.map(lambda g: g / count, grads)


def _squares(x) -> float:
    return float(jnp.sum(jnp.square(x.astype(F32))))


def global_norm(grads) -> float:
    return math.sqrt(sum(_squares(g) for g in jax.tree.leaves(grads)))


def gradient_gaps(grads: Dict[str, Any],
                  reference: Dict[str, Any]) -> Dict[str, float]:
    """``grads`` in the parameter pytree's own shape against
    ``loss_and_grads``' gradient: per kind of parameter (a kind's layers
    taken together), |grads - reference| / |reference|."""
    gaps = {}
    for key, ref in reference.items():
        if key != "layers":
            gaps[key] = math.sqrt(_squares(grads[key] - ref)
                                  / _squares(ref))
            continue
        for kind in ref[0]:
            off = sum(_squares(grads["layers"][kind][j] - layer[kind])
                      for j, layer in enumerate(ref))
            gaps[kind] = math.sqrt(off / sum(_squares(layer[kind])
                                             for layer in ref))
    return gaps


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """For a greedy decoder's ``emitted`` tokens after ``prompt``: at each
    emitted position, the reference's top logit minus the reference's
    logit of the token that was emitted (0 where they agree), one full
    forward pass over prompt + emitted.  ``pad_to`` lengthens the row
    with zeros to one compiled shape: causal attention keeps what follows
    a position from reaching it."""
    seq = list(prompt) + list(emitted)
    seq = np.asarray(seq + [0] * max(0, pad_to - len(seq)), np.int32)[None]
    gap = np.asarray(_gap_jit(logits(params, jnp.asarray(seq), config),
                              seq))[0]
    return gap[len(prompt) - 1:len(prompt) - 1 + len(emitted)]

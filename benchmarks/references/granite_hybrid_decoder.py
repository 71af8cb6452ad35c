"""Plain reference of Granite 4.0-H's hybrid decoder (granite-4.0-h-micro:
``model_type`` granitemoehybrid without experts; transformers'
``modeling_granitemoehybrid`` and Mamba-2, arXiv:2405.21060, as known).

    x = embed[tok] * embedding_multiplier
    per layer:  r = x; h = RMSNorm(x); h = mixer(h); x = r + rm * h
                r = x; h = RMSNorm(x); h = W_down(silu(W_gate h) * W_up h)
                x = r + rm * h                 (rm = residual_multiplier)
    logits = RMSNorm(x) embed^T / logits_scaling

``layer_types`` says which mixer a layer has.

- ``attention``: q, k, v without bias, grouped-query heads, NO positional
  encoding, causal softmax of ``q.k * attention_multiplier``, output
  projection.
- ``mamba`` (Mamba-2, one group): ``[z | xBC | dt] = W_in h``; ``xBC =
  silu(conv(xBC) + b)``, a causal depthwise conv of ``mamba_d_conv``
  taps (zeros before position 0); ``x (T, nh, hd), B, C (T, N)``;
  ``dt = softplus(dt + dt_bias)``; ``A = -exp(A_log)``; the recurrence

      S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t,   y_t = S_t C_t + D x_t

  run as a SEQUENTIAL scan over the positions, one state ``(nh, hd, N)``
  carried from S_0 = 0 -- the definition, not the chunked algorithm the
  program prefills with; ``y = RMSNorm(y * silu(z)) * w`` over the whole
  ``nh * hd``; ``out = W_out y``.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no chunks,
no batching.  It shares nothing with ``ray_tpu/models/`` but the
parameter pytree's key names and layouts, which is how the program hands
over its weights:

    embed_tokens (V, H); layers.{attn_norm, mlp_norm (L, H), w_gate, w_up
    (L, H, F), w_down (L, F, H)} over all L layers; {wq (La, H, Hq*D),
    wk, wv (La, H, Hkv*D), wo (La, Hq*D, H)} over the attention layers;
    {ssm_in (Lm, H, 2*nh*hd + 2*N) and ssm_dt (Lm, H, nh), together the
    published in-projection, ssm_conv_w (Lm, K, conv_dim)
    with tap K-1 on the current position, ssm_conv_b (Lm, conv_dim),
    ssm_dt_bias, ssm_A_log, ssm_D (Lm, nh), ssm_norm (Lm, nh*hd), ssm_out
    (Lm, nh*hd, H)} over the Mamba layers, each in the layers' order;
    final_norm (H,).

Departures from the published description: none in the mathematics.
The published checkpoint stores the conv weight as ``(conv_dim, 1, K)``;
here the taps lead.  ``mamba_n_groups`` other than 1, ``mamba_proj_bias``
and experts are refused.

**Gaps are in units of the logits' deviation.**  ``LOGIT_MARGIN`` in
``kinds/serve_llm.py`` (0.25) is argued for logits of standard deviation
near 1; this model divides its logits by ``logits_scaling`` (8), and on
seeded weights their deviation over the vocabulary is near 0.11, not 1.
``teacher_forced_gap`` therefore divides each position's gap by the
standard deviation of the reference's own logits at that position, so
that 0.25 is as tight here as for the other configurations.
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


def _mlp(x, w, eps, rm):
    h = _rms_norm(x, w["mlp_norm"], eps)
    h = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return x + rm * h


def _attention_layer(x, w, heads, kv_heads, head_dim, scale, eps, rm):
    w = {k: v.astype(F32) for k, v in w.items()}
    b, s, _ = x.shape
    h = _rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"]).reshape(b, s, heads, head_dim)
    k = (h @ w["wk"]).reshape(b, s, kv_heads, head_dim)
    v = (h @ w["wv"]).reshape(b, s, kv_heads, head_dim)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=2)   # query head i reads kv head i//group
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, -1)
    return _mlp(x + rm * (attn @ w["wo"]), w, eps, rm)


def _mamba_layer(x, w, nh, hd, n, eps, rm):
    w = {k: v.astype(F32) for k, v in w.items()}
    b, s, _ = x.shape
    d_inner = nh * hd
    h = _rms_norm(x, w["attn_norm"], eps)
    # the published in-projection [z | xBC | dt], its dt columns apart
    zxbcdt = h @ jnp.concatenate([w["ssm_in"], w["ssm_dt"]], axis=1)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:2 * d_inner + 2 * n]
    dt = zxbcdt[..., 2 * d_inner + 2 * n:]
    taps = w["ssm_conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = w["ssm_conv_b"] + sum(
        padded[:, k:k + s] * w["ssm_conv_w"][k] for k in range(taps))
    xbc = jax.nn.silu(conv)
    xs = xbc[..., :d_inner].reshape(b, s, nh, hd)
    Bs, Cs = xbc[..., d_inner:d_inner + n], xbc[..., d_inner + n:]
    dt = jax.nn.softplus(dt + w["ssm_dt_bias"])              # (B, S, nh)
    A = -jnp.exp(w["ssm_A_log"])

    def position(state, inputs):
        x_t, b_t, c_t, dt_t = inputs          # (B, nh, hd) (B, n) (B, nh)
        state = (jnp.exp(dt_t * A)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None]
                 * b_t[:, None, None, :])
        return state, jnp.einsum("bhdn,bn->bhd", state, c_t)

    _, ys = jax.lax.scan(
        position, jnp.zeros((b, nh, hd, n), F32),
        (xs.transpose(1, 0, 2, 3), Bs.transpose(1, 0, 2),
         Cs.transpose(1, 0, 2), dt.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2, 3) + w["ssm_D"][:, None] * xs
    y = y.reshape(b, s, d_inner) * jax.nn.silu(z)
    y = _rms_norm(y, w["ssm_norm"], eps)
    return _mlp(x + rm * (y @ w["ssm_out"]), w, eps, rm)


_attention_jit = jax.jit(_attention_layer, static_argnums=(2, 3, 4, 5, 6, 7))
_mamba_jit = jax.jit(_mamba_layer, static_argnums=(2, 3, 4, 5, 6))


def _embed(table, tokens, multiplier):
    return table[tokens].astype(F32) * multiplier


def _head(x, final_norm, rows, eps, divisor):
    """Logits of the vocabulary rows ``rows`` (v, H) of the tied table."""
    return _rms_norm(x, final_norm.astype(F32), eps) @ rows.astype(F32).T \
        / divisor


def _gap(lg, tokens):
    """Per position: (the top logit minus the logit of the NEXT token) /
    the standard deviation of the position's logits over the vocabulary."""
    nxt = jnp.roll(tokens, -1, axis=1)
    gap = lg.max(-1) - jnp.take_along_axis(lg, nxt[..., None], -1)[..., 0]
    return gap / lg.std(-1)


_embed_jit = jax.jit(_embed, static_argnums=(2,))
_head_jit = jax.jit(_head, static_argnums=(3, 4))
_gap_jit = jax.jit(_gap)

_HEAD_SLICES = 8
_SHARED = ("attn_norm", "mlp_norm", "w_gate", "w_up", "w_down")
_ATTENTION = ("wq", "wk", "wv", "wo")


def _check(config: Dict[str, Any]) -> None:
    if config.get("mamba_n_groups", 1) != 1 or config.get("mamba_proj_bias") \
            or config.get("num_local_experts", 0):
        raise ValueError("granite_hybrid_decoder: one group, no projection "
                         "bias and no experts are modelled")
    if config.get("position_embedding_type", "nope") != "nope":
        raise ValueError("granite_hybrid_decoder: NoPE only")


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any]):
    """(B, S, V) float32 logits for ``tokens`` (B, S) int32.  ``config``
    is the configuration file's dict (published key names)."""
    _check(config)
    eps = float(config["rms_norm_eps"])
    rm = float(config["residual_multiplier"])
    layers = params["layers"]
    seen = {"attention": 0, "mamba": 0}
    with jax.default_matmul_precision("highest"):
        x = _embed_jit(params["embed_tokens"], tokens,
                       float(config["embedding_multiplier"]))
        for i, kind in enumerate(config["layer_types"]):
            j = seen[kind]
            seen[kind] += 1
            w = {name: layers[name][i] for name in _SHARED}
            if kind == "attention":
                w.update({name: layers[name][j] for name in _ATTENTION})
                x = _attention_jit(
                    x, w, config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"],
                    float(config["attention_multiplier"]), eps, rm)
            else:
                w.update({name: leaf[j] for name, leaf in layers.items()
                          if name.startswith("ssm_")})
                x = _mamba_jit(x, w, config["mamba_n_heads"],
                               config["mamba_d_head"],
                               config["mamba_d_state"], eps, rm)
        if not config["tie_word_embeddings"]:
            raise ValueError("granite_hybrid_decoder: tied head only")
        # the head a slice of the vocabulary at a time: a float32 copy of
        # the whole 100,352 x 2,048 table would not fit beside a full cache
        table = params["embed_tokens"]
        step = -(-table.shape[0] // _HEAD_SLICES)
        return jnp.concatenate([
            _head_jit(x, params["final_norm"], table[i:i + step], eps,
                      float(config["logits_scaling"]))
            for i in range(0, table.shape[0], step)], axis=-1)


def logit_deviation(params: Dict[str, Any], tokens,
                    config: Dict[str, Any]) -> float:
    """The mean over positions of the logits' standard deviation over the
    vocabulary: what ``teacher_forced_gap`` divides by, for the record."""
    return float(jnp.mean(logits(params, tokens, config).std(-1)))


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """For a greedy decoder's ``emitted`` tokens after ``prompt``: at each
    emitted position, the reference's top logit minus the reference's
    logit of the token that was emitted (0 where they agree), IN UNITS OF
    THE STANDARD DEVIATION of the reference's logits at that position
    (module docstring), one full forward pass over prompt + emitted.
    ``pad_to`` lengthens the row with zeros to one compiled shape: causal
    attention and a recurrence that runs forward keep what follows a
    position from reaching it."""
    seq = list(prompt) + list(emitted)
    seq = np.asarray(seq + [0] * max(0, pad_to - len(seq)), np.int32)[None]
    gap = np.asarray(_gap_jit(logits(params, jnp.asarray(seq), config),
                              seq))[0]
    return gap[len(prompt) - 1:len(prompt) - 1 + len(emitted)]

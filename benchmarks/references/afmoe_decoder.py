"""Plain reference of Trinity-Mini's decoder (``model_type: afmoe``: the
published ``config.json`` as the catalog beside the model-configs guide
holds it, and the public ``modeling_afmoe.py`` of ``transformers`` as
known), forward and backward.  Stream ``x`` (S, 2048), eps 1e-5, no biases:

    x0 = E[token] * sqrt(hidden_size)                   (mup_enabled)

    h  = RMSNorm(x; g_attn)
    q, k, v = h W_q, h W_k, h W_v                      32 / 4 / 4 heads of 128
    g  = h W_g                                           4,096: the output gate
    q, k = RMSNorm over each head's 128 (one weight of 128 shared by heads)
    q, k = rotate-half RoPE(theta 10,000)    sliding_attention layers ONLY;
                                             full_attention layers: no position
    a_i = softmax_j(q_i . k_j / sqrt(128)),  j <= i, and on a sliding layer
                                             i - j < sliding_window
    x  = x + RMSNorm(( a * sigmoid(g) ) W_o; g_post_attn)

    h2 = RMSNorm(x; g_mlp)
    layers below num_dense_layers:   f = W_2 (silu(W_1 h2) * W_3 h2)
    the others:
        s = sigmoid(h2 W_r)                              float32, 128 experts
        I = the num_experts_per_tok largest of s + b     (b: the CHOICE only)
        w_i = route_scale * s_i / (sum_{j in I} s_j + 1e-20)   (route_norm)
        f = sum_{i in I} w_i E_i(h2) + E_shared(h2)      each a SwiGLU of 1,024
    x  = x + RMSNorm(f; g_post_mlp)

then a final RMSNorm and the untied head; the loss is the mean next-token
cross-entropy with no auxiliary term.

One chip's SHARE (the file's ``share``): the router scores all
``num_experts_published`` experts and chooses among them; of the chosen,
experts ``experts_first .. experts_first + experts_held`` are computed
here and what the others would add is left out; logits and loss are over
the file's ``vocab_size`` rows.  Without ``share`` every expert is here.

Departures from the published description, each a reading listed in the
configuration file's ``assumed``: muP as the embedding's multiplier alone;
the q/k norm a head BEFORE RoPE; the gate ``sigmoid(W_g h)`` of the normed
input; the router's constants.  None in the mathematics above.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no scan over
layers, no sort, no grouped matmul.  The experts are a LOOP over the held
experts, each computed on every token and weighted by a gate that is zero
where it was not chosen, so it cannot share a routing or dispatch bug with
``ray_tpu/``; attention is a block of queries against every key under the
mask written out.  It shares nothing with ``ray_tpu/models/`` but the
parameter pytree's key names.  The tree holds the layers as the program
walks them, in PARTS (``dense_layers``, then ``layers``, ``layers_1``, ...:
cut where the kind of layer changes), each leaf stacked over its part's
layers:

    embed_tokens (V, H); final_norm (H,); lm_head (H, V); per part
    attn_norm, mlp_norm, post_attn_norm, post_mlp_norm (n, H),
    wq (n, H, Hq*D), wk, wv (n, H, Hkv*D), w_attn_gate (n, H, Hq*D),
    wo (n, Hq*D, H), q_norm, k_norm (n, D)
    w_gate, w_up (n, H, F), w_down (n, F, H)              dense parts
    router (n, H, E), router_bias (n, E), ws_gate, ws_up (n, H, Fe),
    ws_down (n, Fe, H), w_gate, w_up (n, Eh, H, Fe),
    w_down (n, Eh, Fe, H)                                 expert parts

One layer is one jitted call, forward and backward (``jax.vjp`` of the same
``_layer``, a block of queries and an expert at a time recomputed inside
it), so a row of 8,192 never holds more than one layer's weights, one
block's scores (32 x 256 x 8,192 float32 = 268 MB) or one expert's
activations.

``gradient_gaps`` and the flips.  Top-8 of 128 sigmoid scores + bias is a
near-tie for some tokens in every layer, and the program's bfloat16 stream
breaks some the other way than this float32 one: such a token's whole
contribution moves from one expert's matrices to another's.  What that does
to each kind's gap was MEASURED on the chip before the cell was frozen
(``benchmarks/tools/train_check.py``, six seeds and six broken programs;
PERF.md section 2, PR 57): the sound program leaves every other kind at
0.010-0.047 of the reference's (under the harness's 0.1, which stands for
them) but the router at 0.154-0.172 and the routed experts' three matrices
at 0.119-0.130 -- by ``sqrt(2 f / 8)`` a flip rate f of about a twentieth
of the (token, layer) pairs (with the sandwich norms drawn at 1, as they
first were, 0.080, 0.235 and 0.173).  ``FLIP_SENSITIVE`` and ``FLIP_LIMIT``
below give
those four kinds a limit of their own, between that and what a program
wrong in the routed experts' own terms reads.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256
# the published constant (the program shares LFM2's 1e-6: the sum of eight
# sigmoids is of order 4, so the two differ by 2.5e-7 relative)
ROUTE_NORM_EPS = 1e-20
UNTRAINED = ("router_bias",)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x (S, heads, D), positions 0..S-1, rotate-half convention: the
    first half of D is paired with the second."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d // 2, dtype=F32) / (d // 2))
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, w, heads, kv_heads, head_dim, theta, eps, window):
    """h (S, H) normed -> (S, Hq * D), gated, before W_o.  ``window`` 0: a
    full layer (no position); else a sliding layer (RoPE, the band)."""
    s = h.shape[0]
    q = _rms_norm((h @ w["wq"]).reshape(s, heads, head_dim), w["q_norm"],
                  eps)
    k = _rms_norm((h @ w["wk"]).reshape(s, kv_heads, head_dim), w["k_norm"],
                  eps)
    v = (h @ w["wv"]).reshape(s, kv_heads, head_dim)
    if window:
        q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)   # head i reads kv i//group
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    size = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    j = jnp.arange(s)[None, :]

    @jax.checkpoint          # a block's scores are made again in its backward
    def block(qb, i):
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(head_dim)
        seen = j <= i[:, None]
        if window:
            seen &= i[:, None] - j < window
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(lambda args: block(*args), (
        q.reshape(s // size, size, heads, head_dim),
        jnp.arange(s).reshape(s // size, size)))
    return out.reshape(s, heads * head_dim) * jax.nn.sigmoid(
        h @ w["w_attn_gate"])


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _experts(h, w, top_k, scale, first):
    """h (S, H) -> the held experts' part of the routed sum + the shared
    expert.  ``first``: the first held expert's index among the router's."""
    s = jax.nn.sigmoid(h @ w["router"])                       # (S, E)
    _, chosen = jax.lax.top_k(s + w["router_bias"], top_k)
    picked = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], chosen].set(True)
    gates = scale * jnp.where(picked, s, 0.0) / (
        jnp.sum(jnp.where(picked, s, 0.0), -1, keepdims=True)
        + ROUTE_NORM_EPS)
    out = _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    one = jax.checkpoint(_swiglu)
    for e in range(w["w_gate"].shape[0]):
        out = out + gates[:, first + e, None] * one(
            h, w["w_gate"][e], w["w_up"][e], w["w_down"][e])
    return out


def _layer(x, w, window, heads, kv_heads, head_dim, theta, eps, top_k, scale,
           first):
    """x (S, H) -> (S, H).  ``top_k`` 0: a dense layer."""
    h = _rms_norm(x, w["attn_norm"], eps)
    a = _attention(h, w, heads, kv_heads, head_dim, theta, eps, window)
    x = x + _rms_norm(a @ w["wo"], w["post_attn_norm"], eps)
    h = _rms_norm(x, w["mlp_norm"], eps)
    f = _experts(h, w, top_k, scale, first) if top_k \
        else _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    return x + _rms_norm(f, w["post_mlp_norm"], eps)


def _layer_bwd(x, w, dy, *sizes):
    """(dx, dw): the layer is computed again here and pulled back."""
    _, pull = jax.vjp(lambda x, w: _layer(x, w, *sizes), x, w)
    return pull(dy)


_STATIC = tuple(range(2, 11))
_layer_jit = jax.jit(_layer, static_argnums=_STATIC)
_layer_bwd_jit = jax.jit(_layer_bwd,
                         static_argnums=tuple(i + 1 for i in _STATIC))


def _embed(table, tokens, multiplier):
    return table[tokens].astype(F32) * multiplier


def _embed_bwd(table, tokens, dx, multiplier):
    return jnp.zeros(table.shape, F32).at[tokens].add(dx * multiplier)


def _head(x, final_norm, head, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


def _nll_sum(x, final_norm, head, tokens, eps):
    """Summed next-token cross-entropy over every position but the last."""
    logp = jax.nn.log_softmax(_head(x, final_norm, head, eps)[:-1], -1)
    return -jnp.sum(jnp.take_along_axis(logp, tokens[1:, None], -1))


_embed_jit = jax.jit(_embed, static_argnums=(2,))
_embed_bwd_jit = jax.jit(_embed_bwd, static_argnums=(3,))
_head_jit = jax.jit(_head, static_argnums=(3,))
_nll_sum_grad_jit = jax.jit(jax.value_and_grad(_nll_sum, argnums=(0, 1, 2)),
                            static_argnums=(4,))


def _multiplier(config) -> float:
    return math.sqrt(config["hidden_size"]) if config["mup_enabled"] else 1.0


def _part_keys(params, prefix):
    return sorted(
        (k for k in params if k == prefix or (
            k.startswith(prefix + "_") and k[len(prefix) + 1:].isdigit())),
        key=lambda k: int(k[len(prefix) + 1:] or 0))


def _places(params, config):
    """Per layer ``(part key, index in the part)``: the leading dense
    layers under ``dense_layers*``, the others under ``layers*``, in the
    parts' order."""
    out = []
    for prefix in ("dense_layers", "layers"):
        for key in _part_keys(params, prefix):
            n = params[key]["attn_norm"].shape[0]
            out += [(key, i) for i in range(n)]
    if len(out) != config["num_hidden_layers"]:
        raise ValueError(f"afmoe_decoder: the parameters hold {len(out)} "
                         f"layers, the configuration "
                         f"{config['num_hidden_layers']}")
    return out


def _layer_sizes(config, i):
    """``_layer``'s static arguments for layer ``i``."""
    share = config.get("share") or {}
    routed = i >= config["num_dense_layers"]
    return (config["sliding_window"]
            if config["layer_types"][i] == "sliding_attention" else 0,
            config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], float(config["rope_theta"]),
            float(config["rms_norm_eps"]),
            config["num_experts_per_tok"] if routed else 0,
            float(config["route_scale"]),
            share.get("experts_first", 0))


def _weights(params, place):
    key, i = place
    return {name: leaf[i].astype(F32) for name, leaf in params[key].items()}


def _hidden(params, tokens, config):
    """For ONE row of tokens (S,): the stream entering every layer, and
    leaving the last."""
    if not config["route_norm"]:
        raise ValueError("afmoe_decoder: route_norm false is not written")
    xs = [_embed_jit(params["embed_tokens"], tokens, _multiplier(config))]
    for i, place in enumerate(_places(params, config)):
        xs.append(_layer_jit(xs[-1], _weights(params, place),
                             *_layer_sizes(config, i)))
    return xs


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any]):
    """(B, S, V) float32 logits for ``tokens`` (B, S) int32.  ``config``
    is the configuration file's dict (published key names)."""
    eps = float(config["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _head_jit(_hidden(params, jnp.asarray(row), config)[-1],
                      params["final_norm"], params["lm_head"], eps)
            for row in np.asarray(tokens, np.int32)])


def loss_and_grads(params: Dict[str, Any], tokens, config: Dict[str, Any],
                   rows_at_a_time: int = 1, place: Callable = jnp.asarray):
    """Mean next-token cross-entropy of ``tokens`` (B, S) over every
    position but the last of each row, and its gradient in float32, in the
    parameter pytree's own shape (a part's leaves stacked over its layers;
    ``router_bias``, which no gradient reaches, as zeros).  A row at a
    time whatever ``rows_at_a_time`` says (one chip's rows; ``place`` puts
    them on it), a layer at a time backwards."""
    del rows_at_a_time
    eps, mult = float(config["rms_norm_eps"]), _multiplier(config)
    places = _places(params, config)
    total, count = 0.0, 0
    d_layers = [None] * len(places)
    d_rest = None
    with jax.default_matmul_precision("highest"):
        for i in range(tokens.shape[0]):
            row = place(tokens[i:i + 1])[0]
            xs = _hidden(params, row, config)
            nll, (dx, d_norm, d_head) = _nll_sum_grad_jit(
                xs.pop(), params["final_norm"], params["lm_head"], row, eps)
            for j in reversed(range(len(places))):
                dx, dw = _layer_bwd_jit(xs.pop(), _weights(params, places[j]),
                                        dx, *_layer_sizes(config, j))
                d_layers[j] = dw if d_layers[j] is None else jax.tree.map(
                    jnp.add, d_layers[j], dw)
            rest = {"embed_tokens": _embed_bwd_jit(params["embed_tokens"],
                                                   row, dx, mult),
                    "final_norm": d_norm, "lm_head": d_head}
            d_rest = rest if d_rest is None else jax.tree.map(
                jnp.add, d_rest, rest)
            total += float(nll)
            count += row.shape[0] - 1
    grads = {key: value / count for key, value in d_rest.items()}
    for key in {key for key, _ in places}:
        layers = [dw for (k, _), dw in zip(places, d_layers) if k == key]
        grads[key] = {name: jnp.stack([dw[name] for dw in layers]) / count
                      for name in layers[0]}
    return total / count, grads


def _squares(x) -> float:
    return float(jnp.sum(jnp.square(x.astype(F32))))


def global_norm(grads) -> float:
    """Of what the optimizer clips: every leaf but the untrained bias."""
    return math.sqrt(sum(
        _squares(g) for path, g in jax.tree_util.tree_leaves_with_path(grads)
        if path[-1].key not in UNTRAINED))


# The kinds a flipped expert choice moves whole: a routed expert's three
# matrices and the router that chose.  A kind listed here is returned
# scaled so that the harness's 0.1 is FLIP_LIMIT.  The limit lies between
# two readings on the v5e (my chip runs, PR 57; PERF.md section 2): the
# largest a sound program gave over six seeds, 0.172 (router; the matrices
# 0.130; under the first initialiser, norms at 1, 0.235 and 0.173), and the
# smallest a program gave that is wrong in these kinds' own terms, 0.476
# (w_down with the bias left out of the selection; the router 0.630 there;
# under the first initialiser 0.489, and the held range shifted by one
# expert 1.22-1.42, no shared expert 1.32-2.46).  A fault elsewhere raises
# them less -- under the first initialiser RoPE on the full layer
# 0.238-0.318, the band out of dq 0.238-0.351 -- and is caught by the kinds
# that keep 0.1 (there 0.128-0.184 and 0.2-2.95 against a sound 0.080 at
# most, 0.047 as shipped).
FLIP_SENSITIVE = ("router", "w_gate", "w_up", "w_down")
FLIP_LIMIT = 0.35


def gradient_gaps(grads: Dict[str, Any],
                  reference: Dict[str, Any]) -> Dict[str, float]:
    """``grads`` (the program's, in the parameter pytree's shape) against
    ``loss_and_grads``' gradient: per kind of parameter, a kind's layers
    taken together over every part that has it, |grads - reference| /
    |reference|.  A dense part's and an expert part's ``w_gate`` / ``w_up``
    / ``w_down`` are two kinds (``dense.w_gate``, ``w_gate``).
    ``router_bias``, structurally zero on both sides, reads 0 and not
    0/0 -- and its largest magnitude if either side is not zero."""
    off: Dict[str, float] = {}
    size: Dict[str, float] = {}
    for key, ref in reference.items():
        leaves = ref if isinstance(ref, dict) else {key: ref}
        ours = grads[key] if isinstance(ref, dict) else {key: grads[key]}
        for name, leaf in leaves.items():
            kind = "dense." + name if key.startswith("dense_layers") \
                and name in ("w_gate", "w_up", "w_down") else name
            off[kind] = off.get(kind, 0.0) + _squares(ours[name] - leaf)
            size[kind] = size.get(kind, 0.0) + _squares(leaf)
    gaps = {}
    for kind in off:
        if kind in UNTRAINED:
            gaps[kind] = math.sqrt(off[kind] + size[kind])
            continue
        gaps[kind] = math.sqrt(off[kind] / size[kind])
        if kind in FLIP_SENSITIVE:
            gaps[kind] *= 0.1 / FLIP_LIMIT
    return gaps


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """For a greedy decoder's ``emitted`` tokens after ``prompt``: at each
    emitted position, the reference's top logit minus the reference's
    logit of the token that was emitted (0 where they agree), one full
    forward pass over prompt + emitted.  ``pad_to`` lengthens the row with
    zeros to one compiled shape: causal attention keeps what follows a
    position from reaching it."""
    seq = list(prompt) + list(emitted)
    seq = np.asarray(seq + [0] * max(0, pad_to - len(seq)), np.int32)[None]
    lg = logits(params, seq, config)[0]
    nxt = np.roll(seq[0], -1)
    gap = np.asarray(lg.max(-1) - lg[jnp.arange(len(nxt)), nxt])
    return gap[len(prompt) - 1:len(prompt) - 1 + len(emitted)]

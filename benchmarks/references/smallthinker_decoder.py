"""Plain reference of SmallThinker's decoder (SmallThinker-21BA3B-
Instruct: the published ``config.json`` and the model card's description
as the catalog beside the model-configs guide holds them).  For layer l
with input x:

    r   = x W_r                      router logits, from the layer's INPUT,
                                     before the attention norm, un-normed
    h   = RMSNorm(x; g_attn)
    q, k, v = h W_q, h W_k, h W_v    no biases, no q/k norm
    if rope_layout[l]:  q, k = rotate-half RoPE(theta) at absolute positions
    a_i = softmax_j(q_i . k_j / sqrt(head_dim))  over j <= i and, if
          sliding_window_layout[l],  i - j < sliding_window_size
    x'  = x + concat(a) W_o
    h'  = RMSNorm(x'; g_mlp)
    p   = softmax(r) in float32 over ALL experts; top-k; g = p_top / sum p_top
          (norm_topk_prob true; as they are when false)
    y   = sum_{e in top-k} g_e W_down,e (relu(W_gate,e h') * (W_up,e h'))
    out = x' + y

then a final RMSNorm and the untied output head.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no ring, no
sort.  The window is an explicit ``i - j < W`` mask over every key;
EVERY expert is computed on EVERY token and weighted by a gate that is
zero outside the top-k, so it cannot share a routing, ring or band bug
with ``ray_tpu/``.  It shares nothing with ``ray_tpu/models/`` but the
parameter pytree's key names:

    embed_tokens (V, H); layers.{attn_norm (L, H), wq (L, H, Hq*D),
    wk, wv (L, H, Hkv*D), wo (L, Hq*D, H), mlp_norm (L, H),
    router (L, H, E), w_gate, w_up (L, E, H, F), w_down (L, E, F, H)};
    final_norm (H,); lm_head (H, V).

The benchmark pads every checked row to the engine's ``max_len`` (16,384),
where nothing whole fits beside the engine: 28 heads of 16,384 x 16,384
float32 scores are 30 GB, every expert on every token 10.7 GB, the logits
10 GB.  So the same mathematics runs IN BLOCKS, each a plain product:
queries a block at a time against every key, positions a block at a time
through the experts (a group of experts at a time, each group's weights
widened to float32 inside the step), and the head a block of positions
and a slice of the vocabulary at a time, keeping only the top logit and
the next token's.  One layer is one jitted call.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256        # 28 x 256 x 16,384 float32 scores: 470 MB
POSITION_BLOCK = 1024    # through a group of experts / the head
EXPERT_GROUP = 16        # 16 x 3 x 2,560 x 768 float32: 377 MB
# Over this a position's gap is a swap of experts at a near-tie, not
# rounding (largest without a swap 0.022; the harness's margin is 0.25).
SWAP_GAP = 0.05


def swaps_allowed(n: int) -> int:
    """Of a request's ``n`` emitted positions, how many may read over
    SWAP_GAP: 18% (the sound engine's requests read 0-12.1%; broken
    programs 14-24%, 38% and 99.6%) and eight more, which keeps a short
    request's count from deciding by chance (at 12%, 32 positions pass
    13 once in 1e5 requests)."""
    return 8 + 9 * n // 50


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


def _blocks(x, size):
    """(S, ...) -> (S / size, size, ...)."""
    return x.reshape((x.shape[0] // size, size) + x.shape[1:])


def _attention(q, k, v, window):
    """q (S, Hq, D); k, v (S, Hkv, D) -> (S, Hq * D).  A block of queries
    against every key, masked."""
    s, heads, d = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)   # query head i reads kv head i//group
    v = jnp.repeat(v, group, axis=1)
    size = min(QUERY_BLOCK, s)
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, i = args                   # (size, Hq, D), (size,) positions
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(d)
        seen = j <= i[:, None]
        if window:
            seen &= i[:, None] - j < window
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, (_blocks(q, size), _blocks(jnp.arange(s), size)))
    return out.reshape(s, heads * d)


def _experts(h, gates, w_gate, w_up, w_down):
    """h (S, H); gates (S, E), zero outside the top-k; the expert
    matrices as stored.  Every expert on every token."""
    s, e = gates.shape
    size, grp = min(POSITION_BLOCK, s), min(EXPERT_GROUP, e)
    grouped = [w.reshape((e // grp, grp) + w.shape[1:])
               for w in (w_gate, w_up, w_down)]

    def block(args):
        hb, gb = args

        def group(y, ws):
            wg, wu, wd, g = (a.astype(F32) for a in ws)
            act = jax.nn.relu(jnp.einsum("sh,ehf->sef", hb, wg)) \
                * jnp.einsum("sh,ehf->sef", hb, wu)
            return y + jnp.einsum("sef,efh->sh", act * g.T[:, :, None],
                                  wd), None

        return jax.lax.scan(
            group, jnp.zeros_like(hb),
            (*grouped, gb.T.reshape(e // grp, grp, size)))[0]

    return jax.lax.map(block, (_blocks(h, size), _blocks(gates, size))
                       ).reshape(h.shape)


def _layer(x, w, heads, kv_heads, head_dim, theta, eps, top_k, norm_topk,
           rope, window):
    """x (S, H) float32; w: the layer's weights as stored."""
    stacks = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    w = {k: v.astype(F32) for k, v in w.items() if k not in stacks}
    s = x.shape[0]
    router_logits = x @ w["router"]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"]).reshape(s, heads, head_dim)
    k = (h @ w["wk"]).reshape(s, kv_heads, head_dim)
    v = (h @ w["wv"]).reshape(s, kv_heads, head_dim)
    if rope:
        q, k = _rope(q, theta), _rope(k, theta)
    x = x + _attention(q, k, v, window) @ w["wo"]

    h = _rms_norm(x, w["mlp_norm"], eps)
    gates = jax.nn.softmax(router_logits, axis=-1)            # (S, E)
    _, chosen = jax.lax.top_k(gates, top_k)
    member = jax.nn.one_hot(chosen, gates.shape[-1], dtype=F32).sum(-2)
    gates = gates * member              # every expert not chosen: zero
    if norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    return x + _experts(h, gates, **stacks), chosen


# static: everything after the weights
_layer_jit = jax.jit(_layer, static_argnums=tuple(range(2, 11)))


def _embed(table, tokens):
    return table[tokens].astype(F32)


def _head_gap(x, final_norm, head, nxt, eps):
    """Per position: the top logit minus the logit of ``nxt``.  A block
    of positions against a slice of the vocabulary at a time."""
    s, vocab = x.shape[0], head.shape[1]
    size = min(POSITION_BLOCK, s)
    slices = 8 if vocab % 8 == 0 and vocab > 32768 else 1
    width = vocab // slices
    head = head.reshape(head.shape[0], slices, width).transpose(1, 0, 2)
    x = _rms_norm(x, final_norm.astype(F32), eps)

    def block(args):
        xb, nb = args

        def part(carry, hw):
            top, own = carry
            w, first = hw
            lg = xb @ w.astype(F32)                        # (size, width)
            at = jnp.clip(nb - first, 0, width - 1)
            mine = jnp.take_along_axis(lg, at[:, None], -1)[:, 0]
            inside = (nb >= first) & (nb < first + width)
            return (jnp.maximum(top, lg.max(-1)),
                    jnp.where(inside, mine, own)), None

        (top, own), _ = jax.lax.scan(
            part, (jnp.full((size,), -jnp.inf, F32), jnp.zeros((size,), F32)),
            (head, jnp.arange(slices) * width))
        return top - own

    return jax.lax.map(block, (_blocks(x, size), _blocks(nxt, size))
                       ).reshape(s)


def _head(x, final_norm, head, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


_embed_jit = jax.jit(_embed)
_head_jit = jax.jit(_head, static_argnums=(3,))
_head_gap_jit = jax.jit(_head_gap, static_argnums=(4,))


def _sizes(config: Dict[str, Any]):
    if config.get("rope_scaling") is not None:
        raise ValueError("smallthinker_decoder: rope_scaling is not modelled")
    if not config.get("moe_primary_router_apply_softmax", True):
        raise ValueError("smallthinker_decoder: a sigmoid router is not "
                         "modelled")
    if config["tie_word_embeddings"]:
        raise ValueError("smallthinker_decoder: the head is untied")
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], float(config["rope_theta"]),
            float(config["rms_norm_eps"]),
            config["moe_num_active_primary_experts"],
            bool(config["norm_topk_prob"]))


def _padded(tokens):
    """The row lengthened with zeros to whole blocks (what follows a
    position never reaches it)."""
    s = len(tokens)
    if s <= QUERY_BLOCK:
        return tokens
    return np.concatenate([tokens, np.zeros(-s % POSITION_BLOCK, np.int32)])


def _hidden(params, tokens, config):
    """For ONE row of tokens (S,): the last layer's output (S, H) and
    the experts each layer chose (L, S, k)."""
    sizes = _sizes(config)
    x = _embed_jit(params["embed_tokens"], jnp.asarray(tokens))
    chosen = []
    for i in range(config["num_hidden_layers"]):
        x, picked = _layer_jit(
            x, {k: v[i] for k, v in params["layers"].items()}, *sizes,
            bool(config["rope_layout"][i]),
            config["sliding_window_size"]
            if config["sliding_window_layout"][i] else 0)
        chosen.append(picked)
    return x, jnp.stack(chosen)


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any]):
    """(B, S, V) float32 logits for ``tokens`` (B, S) int32.  ``config``
    is the configuration file's dict (published key names).  The whole
    vocabulary at every position: for short rows."""
    tokens = np.asarray(tokens, np.int32)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _head_jit(_hidden(params, _padded(row), config)[0][:len(row)],
                      params["final_norm"], params["lm_head"],
                      float(config["rms_norm_eps"]))
            for row in tokens])


def teacher_forced_report(params: Dict[str, Any], prompt, emitted,
                          config: Dict[str, Any], pad_to: int = 0):
    """For a greedy decoder's ``emitted`` tokens after ``prompt``, one
    full forward pass over prompt + emitted.  Per emitted token, at the
    position that produced it: ``gap``, the reference's top logit minus
    the reference's logit of the token that was emitted (0 where they
    agree); and ``chosen`` (L, n, k), the experts each layer of the
    reference chose there.  ``pad_to``
    lengthens the row with zeros to one compiled shape: causal attention
    keeps what follows a position from reaching it, and an expert layer
    mixes no positions."""
    seq = list(prompt) + list(emitted)
    seq = _padded(np.asarray(seq + [0] * max(0, pad_to - len(seq)),
                             np.int32))
    at = slice(len(prompt) - 1, len(prompt) - 1 + len(emitted))
    with jax.default_matmul_precision("highest"):
        x, chosen = _hidden(params, seq, config)
        gap = np.asarray(_head_gap_jit(
            x, params["final_norm"], params["lm_head"],
            jnp.asarray(np.roll(seq, -1)), float(config["rms_norm_eps"])))
    return {"gap": gap[at], "chosen": np.asarray(chosen)[:, at]}


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """``teacher_forced_report``'s gap at each emitted position, with the
    near-tie swaps of a request taken out.

    Top-6 of 64 near-uniform probabilities is a near-tie somewhere in the
    stack for most tokens, and an engine in bfloat16 breaks some of them
    the other way: it then adds ANOTHER expert's output, the layers after
    it route on a stream that differs, and the gap at that position is
    whatever a swap of experts makes it, not rounding.  Measured on the
    chip at the published widths (PERF.md section 6, PR 32): the
    engine's top-6 set differs from this reference's in some layer at
    28-33% of positions; where it does not, the largest gap is 0.022;
    where it does, the gap is 0 (the same token leads) or up to 1.06, and
    0-12.1% of a request's positions read over 0.05.  The reference cannot
    know which side the engine took, so a request may hold up to
    ``swaps_allowed(n)`` positions over SWAP_GAP, which are then set to
    zero; a request with more is given back as it was read, and what
    moves every position fails by its raw gaps: weights in float8's
    mantissa (38% of positions over 0.05), the router after attention
    (99.6%), window layers without RoPE on three prompts of four (21-24%;
    14% after an 11,000-token prompt, which passes: under random weights
    the rotation of a window's keys hardly moves a long context)."""
    return take_out_swaps(teacher_forced_report(
        params, prompt, emitted, config, pad_to)["gap"])


def take_out_swaps(gap: np.ndarray) -> np.ndarray:
    """A request's gaps with those over SWAP_GAP set to zero, if they are
    at most ``swaps_allowed``; as they were read if they are more."""
    swapped = gap > SWAP_GAP
    if swapped.sum() > swaps_allowed(len(gap)):
        return gap
    return np.where(swapped, 0.0, gap)

"""Plain reference of Keye-VL-2.0-30B-A3B's language model (the published
``config.json`` as the catalog beside the model-configs guide holds it; the
indexer as ``described_as`` names it, DeepSeek-V3.2-Exp's "lightning
indexer"; text-only positions, the vision tower not served).  For layer l
with input x at position t, ``h = RMSNorm(x; g_attn)``:

    q_i = RoPE(RMSNorm_head(h W_q)_i)   32 heads x 128; k_g likewise and
    v_g = (h W_v)_g                     4 kv heads x 128; theta 1e7
    qI_j = (h W_qI)_j   j = 1..16, 64 wide;  kI = h W_kI  ONE key, 64 wide;
    w = h W_w  (16,)
    I_{t,s} = sum_j w_{t,j} relu(qI_{t,j} . kI_s)           for s <= t
    S_t = the topk = 2,048 positions s <= t of largest I_{t,s}, of equal
          ones the LOWER positions first; every s <= t while t < topk
    o_i = sum_{s in S_t} softmax_{s in S_t}(q_i . k_{s,g(i)} / sqrt(128))
          v_{s,g(i)};   x' = x + concat(o) W_o
    h' = RMSNorm(x'; g_mlp);  p = softmax(h' W_r) in float32 over all 128
    experts; the 8 largest; g = p_top / sum p_top (norm_topk_prob true)
    out = x' + sum_{e in top-8} g_e W_down,e (silu(W_gate,e h') * W_up,e h')

then a final RMSNorm and the untied output head.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no index-key
pool, no gather, no bisection.  The selection is a SORT of the causal row:
the k-th largest score is read off the sorted row, everything above it is
taken, and of the scores equal to it the first by position until the k are
full (a running count); the attention is a softmax over every key with the
others masked.  EVERY expert is computed on EVERY token and weighted by a
gate that is zero outside the top-k.  It shares nothing with
``ray_tpu/models/`` but the parameter pytree's key names:

    embed_tokens (V, H); layers.{attn_norm (L, H), wq (L, H, Hq*D), wk, wv
    (L, H, Hkv*D), wo (L, Hq*D, H), q_norm, k_norm (L, D), wq_idx (L, H,
    Hi*Di), wk_idx (L, H, Di), ww_idx (L, H, Hi), mlp_norm (L, H), router
    (L, H, E), w_gate, w_up (L, E, H, F), w_down (L, E, F, H)}; final_norm
    (H,); lm_head (H, V).

``teacher_forced_report`` runs the equations TWICE over a request: in
float32 throughout (what an engine's tokens are held to), and with every
tensor that the published model STORES -- the stream, a norm's result, a
projection's, K, V and index keys, an expert's hidden row -- rounded to the
type the weights are held in (the configuration's ``torch_dtype``,
bfloat16), float32 inside every operation.  Both are this model; where
their leading tokens lie further apart than the harness's margin the
REQUEST leaves the token undecided, and ``take_out_undecided`` says what
that allows an engine.

The benchmark pads every checked row to the engine's ``max_len`` (16,384),
where nothing whole fits beside the engine, so the same mathematics runs IN
BLOCKS, each a plain product: queries a block at a time against every key
(index scores, the sort and the attention), positions a block at a time
through the experts (a group of experts at a time) and the head (a slice of
the vocabulary at a time, keeping only the top logit and the next token's).
One layer is one jitted call.
"""

from __future__ import annotations

import json
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MARGIN = 0.25            # kinds/serve_llm.py's LOGIT_MARGIN
QUERY_BLOCK = 256        # 32 x 256 x 16,384 float32 scores: 537 MB
POSITION_BLOCK = 1024    # through a group of experts / the head
EXPERT_GROUP = 16        # 16 x 3 x 2,048 x 768 float32: 302 MB


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


def selected(index, seen, topk: int):
    """index (Q, S) float32 scores, seen (Q, S) bool (s <= t) -> bool (Q,
    S): per row the ``topk`` seen keys of largest score, equal scores to
    the lower position; every seen key of a row that has no more."""
    s = index.shape[-1]
    if s <= topk:
        return seen
    index = jnp.where(seen, index, -jnp.inf)
    kth = jnp.sort(index, axis=-1)[:, s - topk, None]
    above = index > kth
    equal = index == kth
    room = topk - above.sum(-1, keepdims=True)
    return seen & (above | (equal & (jnp.cumsum(equal, axis=-1) <= room)))


def _stored(x, store):
    """x as the tensor it is STORED as: rounded to ``store`` (the
    configuration's ``torch_dtype`` where the weights are held in it) and
    read back as float32; as it is where ``store`` is None."""
    return x if store is None else x.astype(store).astype(F32)


def _attention(q, k, v, qi, ki, w, topk, keep_selected):
    """q (S, Hq, D); k, v (S, Hkv, D); qi (S, Hi, Di); ki (S, Di); w (S,
    Hi) -> ((S, Hq * D), selected (S, S) bool if asked for).  A block of
    queries against every key."""
    s, heads, d = q.shape
    group = heads // k.shape[1]
    k = jnp.repeat(k, group, axis=1)   # query head i reads kv head i//group
    v = jnp.repeat(v, group, axis=1)
    size = min(QUERY_BLOCK, s)
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, qib, wb, i = args          # i (size,): the queries' positions
        seen = j <= i[:, None]
        index = jnp.einsum("qjd,kd->qjk", qib, ki)
        index = (jax.nn.relu(index) * wb[:, :, None]).sum(1)
        chosen = selected(index, seen, topk)
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(chosen[None], scores, -jnp.inf), -1)
        return (jnp.einsum("hqk,khd->qhd", probs, v),
                chosen if keep_selected else None)

    out, chosen = jax.lax.map(block, (
        _blocks(q, size), _blocks(qi, size), _blocks(w, size),
        _blocks(jnp.arange(s), size)))
    return (out.reshape(s, heads * d),
            chosen.reshape(s, s) if keep_selected else None)


def _experts(h, gates, w_gate, w_up, w_down, store=None):
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
            act = _stored(
                jax.nn.silu(_stored(jnp.einsum("sh,ehf->sef", hb, wg),
                                    store))
                * _stored(jnp.einsum("sh,ehf->sef", hb, wu), store), store)
            return y + jnp.einsum("sef,efh->sh", act * g.T[:, :, None],
                                  wd), None

        return jax.lax.scan(
            group, jnp.zeros_like(hb),
            (*grouped, gb.T.reshape(e // grp, grp, size)))[0]

    return jax.lax.map(block, (_blocks(h, size), _blocks(gates, size))
                       ).reshape(h.shape)


def _layer(x, w, heads, kv_heads, head_dim, theta, eps, top_k, norm_topk,
           index_heads, index_dim, index_topk, keep_selected, store=None):
    """x (S, H) float32; w: the layer's weights as stored."""
    stacks = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    w = {k: v.astype(F32) for k, v in w.items() if k not in stacks}
    s = x.shape[0]

    def st(a):
        return _stored(a, store)

    h = st(_rms_norm(x, w["attn_norm"], eps))
    q = st(_rms_norm(st(h @ w["wq"]).reshape(s, heads, head_dim),
                     w["q_norm"], eps))
    k = st(_rms_norm(st(h @ w["wk"]).reshape(s, kv_heads, head_dim),
                     w["k_norm"], eps))
    v = st(h @ w["wv"]).reshape(s, kv_heads, head_dim)
    attn, chosen = _attention(
        st(_rope(q, theta)), st(_rope(k, theta)), v,
        st(h @ w["wq_idx"]).reshape(s, index_heads, index_dim),
        st(h @ w["wk_idx"]), h @ w["ww_idx"], index_topk, keep_selected)
    x = st(x + st(st(attn) @ w["wo"]))

    h = st(_rms_norm(x, w["mlp_norm"], eps))
    gates = jax.nn.softmax(h @ w["router"], axis=-1)           # (S, E)
    _, experts = jax.lax.top_k(gates, top_k)
    member = jax.nn.one_hot(experts, gates.shape[-1], dtype=F32).sum(-2)
    gates = gates * member              # every expert not chosen: zero
    if norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    return (st(x + st(_experts(h, gates, store=store, **stacks))), experts,
            chosen)


# static: everything after the weights
_layer_jit = jax.jit(_layer, static_argnums=tuple(range(2, 14)))


def _embed(table, tokens):
    return table[tokens].astype(F32)


def _head_gap(x, final_norm, head, nxt, eps, store=None):
    """Per position: the top logit minus the logit of ``nxt``, and the
    token of the top logit.  A block of positions against a slice of the
    vocabulary at a time."""
    s, vocab = x.shape[0], head.shape[1]
    size = min(POSITION_BLOCK, s)
    slices = 8 if vocab % 8 == 0 and vocab > 32768 else 1
    width = vocab // slices
    head = head.reshape(head.shape[0], slices, width).transpose(1, 0, 2)
    x = _stored(_rms_norm(x, final_norm.astype(F32), eps), store)

    def block(args):
        xb, nb = args

        def part(carry, hw):
            top, best, own = carry
            w, first = hw
            lg = xb @ w.astype(F32)                        # (size, width)
            at = jnp.clip(nb - first, 0, width - 1)
            mine = jnp.take_along_axis(lg, at[:, None], -1)[:, 0]
            inside = (nb >= first) & (nb < first + width)
            here = lg.max(-1)
            return (jnp.maximum(top, here),
                    jnp.where(here > top, first + lg.argmax(-1), best),
                    jnp.where(inside, mine, own)), None

        (top, best, own), _ = jax.lax.scan(
            part, (jnp.full((size,), -jnp.inf, F32),
                   jnp.zeros((size,), jnp.int32), jnp.zeros((size,), F32)),
            (head, jnp.arange(slices, dtype=jnp.int32) * width))
        return top - own, best

    gap, best = jax.lax.map(block, (_blocks(x, size), _blocks(nxt, size)))
    return gap.reshape(s), best.reshape(s)


def _head(x, final_norm, head, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


_embed_jit = jax.jit(_embed)
_head_jit = jax.jit(_head, static_argnums=(3,))
_head_gap_jit = jax.jit(_head_gap, static_argnums=(4, 5))


def _sizes(config: Dict[str, Any]):
    scaling = config.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise ValueError("keye_sparse_decoder: only the default rope "
                         "(mrope_section at text-only positions is the "
                         "plain table) is modelled")
    if config["tie_word_embeddings"] or config.get("attention_bias"):
        raise ValueError("keye_sparse_decoder: the head is untied and the "
                         "projections have no bias")
    if config.get("mlp_only_layers") or config.get(
            "decoder_sparse_step", 1) != 1:
        raise ValueError("keye_sparse_decoder: every layer has experts")
    sa = config["sa_config"]
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("keye_sparse_decoder: one index key a token")
    return (config["num_attention_heads"], config["num_key_value_heads"],
            config["head_dim"], float(config["rope_theta"]),
            float(config["rms_norm_eps"]), config["num_experts_per_tok"],
            bool(config["norm_topk_prob"]), sa["indexer_num_heads"],
            sa["indexer_head_dim"], sa["topk"])


def _padded(tokens):
    """The row lengthened with zeros to whole blocks (what follows a
    position never reaches it)."""
    s = len(tokens)
    if s <= QUERY_BLOCK:
        return tokens
    return np.concatenate([tokens, np.zeros(-s % POSITION_BLOCK, np.int32)])


def _hidden(params, tokens, config, keep_selected=False, store=None):
    """For ONE row of tokens (S,): the last layer's output (S, H), the
    experts each layer chose (L, S, k) and, asked for, the keys each
    layer's queries selected (L, S, S) bool.  ``store``: the type every
    STORED tensor is rounded to (``_stored``); None for float32
    throughout."""
    sizes = _sizes(config)
    x = _embed_jit(params["embed_tokens"], jnp.asarray(tokens))
    experts, chosen = [], []
    for i in range(config["num_hidden_layers"]):
        x, picked, keys = _layer_jit(
            x, {k: v[i] for k, v in params["layers"].items()}, *sizes,
            keep_selected, store)
        experts.append(picked)
        chosen.append(keys)
    return x, jnp.stack(experts), (jnp.stack(chosen) if keep_selected
                                   else None)


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


def selected_keys(params: Dict[str, Any], tokens, config: Dict[str, Any]):
    """(L, S, S) bool for ONE row ``tokens`` (S,): layer l's query t
    attends key s.  For short rows (tests, ``tools/dsa_check.py``)."""
    row = np.asarray(tokens, np.int32)
    with jax.default_matmul_precision("highest"):
        chosen = _hidden(params, _padded(row), config, True)[2]
    return np.asarray(chosen)[:, :len(row), :len(row)]


def teacher_forced_report(params: Dict[str, Any], prompt, emitted,
                          config: Dict[str, Any], pad_to: int = 0):
    """For a greedy decoder's ``emitted`` tokens after ``prompt``, one
    full forward pass over prompt + emitted.  Per emitted token, at the
    position that produced it: ``gap``, the reference's top logit minus
    the reference's logit of the token that was emitted (0 where they
    agree); ``experts`` (L, n, k), the experts each layer of the reference
    chose there; and ``own_gap``, the same gap of the token that the SAME
    equations lead with when every stored tensor is rounded to the type
    the weights are held in (``_stored``: the configuration's
    ``torch_dtype``, bfloat16) -- how far this request's tokens are
    decided at all (zeros for float32 weights, where the two passes are
    one).  ``pad_to`` lengthens the row with zeros to one compiled shape:
    causal attention and a selection among the keys before a position
    keep what follows it from reaching it, and an expert layer mixes no
    positions."""
    seq = list(prompt) + list(emitted)
    seq = _padded(np.asarray(seq + [0] * max(0, pad_to - len(seq)),
                             np.int32))
    at = slice(len(prompt) - 1, len(prompt) - 1 + len(emitted))
    eps = float(config["rms_norm_eps"])
    ends = params["final_norm"], params["lm_head"]
    store = params["embed_tokens"].dtype
    with jax.default_matmul_precision("highest"):
        x, experts, _ = _hidden(params, seq, config)
        nxt = np.roll(seq, -1)
        gap = np.asarray(_head_gap_jit(x, *ends, jnp.asarray(nxt), eps)[0])
        own = np.zeros_like(gap)
        if store != F32:
            rounded = _hidden(params, seq, config, store=store)[0]
            nxt[at] = np.asarray(_head_gap_jit(
                rounded, *ends, jnp.asarray(nxt), eps, store)[1])[at]
            del rounded
            own = np.asarray(_head_gap_jit(x, *ends, jnp.asarray(nxt),
                                           eps)[0])
    return {"gap": gap[at], "own_gap": own[at],
            "experts": np.asarray(experts)[:, at]}


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """``teacher_forced_report``'s gap at each emitted position, with the
    positions that this request leaves undecided taken out
    (``take_out_undecided``); what the raw gaps held goes to stdout, for a
    person."""
    report = teacher_forced_report(params, prompt, emitted, config, pad_to)
    print(json.dumps({"event": "gap_counts", "prompt_tokens": len(prompt),
                      **gap_counts(report["gap"], report["own_gap"])}),
          flush=True)
    return take_out_undecided(report["gap"], report["own_gap"])


def undecided_allowed(own_gap: np.ndarray) -> int:
    """How many of a request's positions may read over the margin: 2.75
    times as many as the model's own second pass reads over it, and 14.
    Between two readings each (chip, PERF.md section 6, PR 45): a sound
    engine's requests read 0.4-2.8 times the second pass's count S (the
    first 46 requests: 25 over 9 and 51 over 26 the furthest out, at most 9
    where S is under 4: 0.65 of this allowance at most); weights in float8's
    mantissa read 16 over 0, 72 over 8, 216 over 36 and 212 over 51 (1.14
    to 2 times the allowance; 35 over 14 is inside it), every key attended
    32 over 5 to 523 over 40 (1.15 to 4.2 times)."""
    return 14 + 11 * int((own_gap > MARGIN).sum()) // 4


def gap_counts(gap: np.ndarray, own_gap: np.ndarray) -> Dict[str, Any]:
    """What a request's raw gaps hold, for a person."""
    return {"positions": int(len(gap)), "raw_max": float(gap.max()),
            "over_0.05": int((gap > 0.05).sum()),
            "over_margin": int((gap > MARGIN).sum()),
            "own_over_0.05": int((own_gap > 0.05).sum()),
            "own_over_margin": int((own_gap > MARGIN).sum()),
            "undecided_allowed": undecided_allowed(own_gap)}


def take_out_undecided(gap: np.ndarray, own_gap: np.ndarray) -> np.ndarray:
    """A request's gaps with those over MARGIN set to zero, if they are at
    most ``undecided_allowed``; as they were read otherwise.

    Under random weights this model does not decide its own tokens to
    within the harness's margin.  Two near-ties are broken by rounding:
    the 8th and 9th of a token's 128 router probabilities lie ~0.06 logits
    apart at the median and a chosen expert carries an eighth of a layer's
    FFN, which is most of the stream; and the index scores have no
    structure, so the 2,048th and 2,049th of several thousand lie close,
    and a few keys of 2,048 swapped move the attention's result by some
    percent.  Each feeds the other in the next layer: on the chip (PERF.md
    section 6, PR 45) the sets a bfloat16 engine selects are the float32
    reference's to ~5 keys of 2,048 in layer 0 and differ by 360-720 keys
    from layer 1 on, and 30-70% of the positions route to another set of
    experts.  The leading token then differs at 3-8% of a request's
    positions by more than the margin -- or at hardly any, where the
    request's tokens are decided firmly (greedy tokens in a cycle, probably:
    not looked at).  How many is a
    property of the REQUEST (its weights, its length, its tokens), so the
    reference measures it on the request itself: ``own_gap`` is what the
    same equations, with every stored tensor rounded to the weights' type,
    read against this float32 pass.  A sound engine is a third such
    computation and reads about as many positions over the margin as that
    second pass does (E - S from -8 to +25 over the first 46 requests,
    chip);
    weights rounded to float8's mantissa read 2.5-9 times as many, every
    key attended 6-13 times, the most recent keys or a reused slot's stale
    index keys every position.
    So up to ``undecided_allowed`` positions over the margin are set to
    zero; a request with more is given back as it was read, and fails by
    its raw gaps.  What this cannot see: a fault on a request whose tokens
    are decided that firmly (every key attended moved 3 of 256 leading
    tokens on one), and one that moves fewer positions than the allowance,
    like a single key of 2,048 scored before its write."""
    wild = gap > MARGIN
    if wild.sum() > undecided_allowed(own_gap):
        return gap
    return np.where(wild, 0.0, gap)

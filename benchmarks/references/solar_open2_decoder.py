"""Plain reference of Solar-Open2-250B's decoder (``model_type:
solar_open2``: the published ``config.json`` as the catalog beside the
model-configs guide holds it; the layer equations as Kimi Linear's,
arXiv:2510.26692, and the public flash-linear-attention ``kda`` layer write
them, each reading listed in the configuration file's ``assumed``).  Every
layer is pre-norm, no biases:

    x <- x + Mixer(RMSNorm(x; g_attn));  x <- x + FFN(RMSNorm(x; g_mlp))

Mixer, a layer of ``gqa_layers`` (softmax attention, NO rotation):

    q, k, v = h W_q, h W_k, h W_v          64 / 8 / 8 heads of 128
    a_i = softmax_j(q_i . k_j / sqrt(128)), j <= i
    out = (concat(a) * sigmoid(h W_gate)) W_o          (use_gqa_gate)

Mixer, the others (KDA, a gated delta rule; H = 64 heads, d = 128):

    [q~ | k~ | v~] = silu(conv4(h W_qkv))    causal, depthwise, a channel,
                                             zeros before position 0
    q = l2norm_head(q~) d^-1/2;  k = l2norm_head(k~);  v = v~
    [f | z | beta] = h W_low                 (128 | 128 | 64)
    g = -exp(A_log_h) softplus(f W_g2 + dt_bias)      (H, d), <= 0
    b = 2 sigmoid(beta)                               (kda_allow_neg_eigval)
    S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T     S_0 = 0
    o_t = S_t^T q_t
    out = [rmsnorm_head(o_t; w) * sigmoid(z W_z2)] W_o

FFN, every layer (``first_k_dense_replace`` 0):

    s = sigmoid(h W_r)                     float32, all 320 experts
    chosen = the 8 largest of s + bias     (the bias in the CHOICE only)
    gate = s[chosen] / (sum s[chosen] + 1e-6) x routed_scaling_factor
    y = S(h) + sum_{e in chosen} gate_e E_e(h)        S, E_e: SwiGLU

then a final RMSNorm and the untied output head.

THE SHARE (configs/solar-open2-250b.json ``share``): this chip is one of
the chips that share each layer and holds experts ``experts_first ..
experts_first + experts_held`` of the published ``n_routed_experts``.  The
router scores all of them; the sum runs over the chosen experts that are
HELD, and what the others would add is left out -- in the program and here
alike.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no state
handed on, no sort, no grouped matmul.  The delta rule is the recurrence as
it is written, ONE POSITION AFTER ANOTHER by ``lax.scan`` (not the chunked
form the program's prefill runs), a group of heads at a time; the attention
a full causal softmax, a block of queries against every key; the experts a
LOOP over the held range, each computed on every token under a gate that is
zero where it was not chosen.  It shares nothing with ``ray_tpu/models/``
but the parameter tree's key names, each leaf stacked over the layers that
have it, in the layers' order:

    embed_tokens (V, D); lm_head (D, V); final_norm (D,); layers:
    attn_norm, mlp_norm (L, D); router (L, D, E); router_bias (L, E);
    w_gate, w_up (L, held, D, F); w_down (L, held, F, D);
    ws_gate, ws_up (L, D, Fs); ws_down (L, Fs, D)          every layer
    wq (La, D, Hq d); wk, wv (La, D, Hkv d); wo (La, Hq d, D);
    w_attn_gate (La, D, Hq d)                              attention
    kda_qkv (Lk, D, 3 H d) columns [q | k | v]; kda_conv_w (Lk, 4, 3 H d)
    oldest tap first; kda_low (Lk, D, 2 R + H) columns [f | z | beta];
    kda_g2, kda_z2 (Lk, R, H d); kda_A_log (Lk, H); kda_dt_bias (Lk, H d);
    kda_norm (Lk, d); kda_o (Lk, H d, D)                   KDA

The benchmark pads every checked row to the engine's ``max_len`` (16,384)
and runs this beside the loaded engine, so a layer is a few jitted calls
over a group of heads or a block of positions each, and the head runs a
block of positions against a slice of the vocabulary at a time, keeping
only the top logit and the next token's.
"""

from __future__ import annotations

import json
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256        # 8 heads x 256 x 16,384 float32 scores: 134 MB
POSITION_BLOCK = 1024
HEAD_GROUP = 16          # KDA heads a call: 4 x (16,384, 16 x 128) float32
VOCAB_SLICES = 4
EXPERT_STACKS = ("w_gate", "w_up", "w_down")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "w_attn_gate")


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _blocks(x, size):
    """(S, ...) -> (S / size, size, ...)."""
    return x.reshape((x.shape[0] // size, size) + x.shape[1:])


# ---------------------------------------------------------------- mixers
def _attention_group(h, wq, wk, wv, gate_w, head_dim):
    """One kv head's queries: h (S, D) normed, wq, gate_w (D, g d), wk, wv
    (D, d) -> the gated attention output (S, g d)."""
    s = h.shape[0]
    q = (h @ wq.astype(F32)).reshape(s, -1, head_dim)
    k, v = h @ wk.astype(F32), h @ wv.astype(F32)
    size = min(QUERY_BLOCK, s)
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, i = args
        scores = jnp.einsum("qhd,kd->hqk", qb, k) / np.sqrt(head_dim)
        probs = jax.nn.softmax(
            jnp.where((j <= i[:, None])[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,kd->qhd", probs, v)

    out = jax.lax.map(block, (_blocks(q, size),
                              _blocks(jnp.arange(s), size)))
    return out.reshape(s, -1) * jax.nn.sigmoid(h @ gate_w.astype(F32))


def _kda_group(h, wq, wk, wv, conv_q, conv_k, conv_v, low, g2, z2, a_log,
               dt_bias, norm_w, head_dim, rank, eps):
    """A group of heads of a KDA mixer: h (S, D) normed; wq, wk, wv (D, n
    d); conv_* (taps, n d); low (S, 2 R + H) the layer's low-rank
    in-projection, its beta columns already the group's (S, 2 R + n); g2,
    z2 (R, n d); a_log (n,); dt_bias (n d,); norm_w (d,).  -> the gated,
    normed output (S, n d)."""
    s, d = h.shape[0], head_dim

    def conv_act(x, w):
        """silu of the causal depthwise conv: tap j reads x_{t-(K-1)+j}."""
        taps = w.shape[0]
        acc = jnp.zeros_like(x)
        for j in range(taps):
            back = taps - 1 - j
            acc = acc + w[j] * jnp.pad(x, ((back, 0), (0, 0)))[:s]
        return jax.nn.silu(acc)

    def heads(x):
        return x.reshape(s, -1, d)

    q = _l2norm(heads(conv_act(h @ wq.astype(F32), conv_q))) * d ** -0.5
    k = _l2norm(heads(conv_act(h @ wk.astype(F32), conv_k)))
    v = heads(conv_act(h @ wv.astype(F32), conv_v))
    f, z, beta = low[:, :rank], low[:, rank:2 * rank], low[:, 2 * rank:]
    g = heads(jax.nn.softplus(f @ g2.astype(F32) + dt_bias)) \
        * -jnp.exp(a_log)[:, None]
    b = 2.0 * jax.nn.sigmoid(beta)

    def position(S, x):
        q_t, k_t, v_t, g_t, b_t = x                 # (n, d) ...; b_t (n,)
        S = jnp.exp(g_t)[:, :, None] * S
        r = jnp.einsum("nk,nkv->nv", k_t, S)
        S = S + (b_t[:, None] * k_t)[:, :, None] * (v_t - r)[:, None, :]
        return S, jnp.einsum("nk,nkv->nv", q_t, S)

    _, o = jax.lax.scan(position, jnp.zeros((q.shape[1], d, d), F32),
                        (q, k, v, g, b))
    o = _rms_norm(o, norm_w, eps).reshape(s, -1)
    return o * jax.nn.sigmoid(z @ z2.astype(F32))


_attention_jit = jax.jit(_attention_group, static_argnums=(5,))
_kda_jit = jax.jit(_kda_group, static_argnums=(13, 14, 15))


def _norm_in(x, scale, eps):
    return _rms_norm(x, scale.astype(F32), eps)


def _add_projected(x, y, w):
    return x + y @ w.astype(F32)


_norm_jit = jax.jit(_norm_in, static_argnums=(2,))
_low_jit = jax.jit(lambda h, w: h @ w.astype(F32))
_add_projected_jit = jax.jit(_add_projected)


def _attention(x, w, c):
    """x (S, D) -> x + the gated attention, a kv head's queries a call."""
    eps, d = float(c["rms_norm_eps"]), c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    h = _norm_jit(x, w["attn_norm"], eps)
    g = heads // kv * d
    out = jnp.concatenate([
        _attention_jit(h, w["wq"][:, i * g:(i + 1) * g],
                       w["wk"][:, i * d:(i + 1) * d],
                       w["wv"][:, i * d:(i + 1) * d],
                       w["w_attn_gate"][:, i * g:(i + 1) * g], d)
        for i in range(kv)], -1)
    return _add_projected_jit(x, out, w["wo"])


def _kda(x, w, c):
    """x (S, D) -> x + the KDA mixer, HEAD_GROUP heads a call."""
    eps = float(c["rms_norm_eps"])
    lin = c["linear_attn_config"]
    H, d = lin["num_heads"], lin["head_dim"]
    rank = (w["kda_low"].shape[1] - H) // 2
    hd = H * d
    n = min(HEAD_GROUP, H)
    h = _norm_jit(x, w["attn_norm"], eps)
    low = _low_jit(h, w["kda_low"])
    outs = []
    for first in range(0, H, n):
        cols = slice(first * d, (first + n) * d)

        def part(leaf, which):           # a projection's columns: q, k or v
            return leaf[..., which * hd + cols.start:which * hd + cols.stop]

        outs.append(_kda_jit(
            h, *(part(w["kda_qkv"], i) for i in range(3)),
            *(part(w["kda_conv_w"], i).astype(F32) for i in range(3)),
            jnp.concatenate([low[:, :2 * rank],
                             low[:, 2 * rank + first:2 * rank + first + n]],
                            -1),
            w["kda_g2"][:, cols], w["kda_z2"][:, cols],
            w["kda_A_log"][first:first + n].astype(F32),
            w["kda_dt_bias"][cols].astype(F32), w["kda_norm"].astype(F32),
            d, rank, eps))
    return _add_projected_jit(x, jnp.concatenate(outs, -1), w["kda_o"])


# ------------------------------------------------------------------- FFN
def _ffn(x, w, eps, first, top_k, norm_topk, scale):
    """x (S, D) -> (x + S(h) + the HELD chosen experts' gated sum, the
    experts chosen (S, k))."""
    h = _rms_norm(x, w["mlp_norm"].astype(F32), eps)
    scores = jax.nn.sigmoid(h @ w["router"].astype(F32))
    _, chosen = jax.lax.top_k(scores + w["router_bias"].astype(F32), top_k)
    member = jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32).sum(-2)
    gates = scores * member
    if norm_topk:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-6)
    gates = gates * scale
    held = w["w_gate"].shape[0]

    def swiglu(wg, wu, wd):
        return (jax.nn.silu(h @ wg.astype(F32)) * (h @ wu.astype(F32))) \
            @ wd.astype(F32)

    def one(y, ws):
        wg, wu, wd, g = ws
        return y + g[:, None] * swiglu(wg, wu, wd), None

    y = swiglu(w["ws_gate"], w["ws_up"], w["ws_down"])
    y = jax.lax.scan(one, y, (w["w_gate"], w["w_up"], w["w_down"],
                              gates[:, first:first + held].T))[0]
    return x + y, chosen


_ffn_jit = jax.jit(_ffn, static_argnums=(2, 3, 4, 5, 6))


# ------------------------------------------------------------------ head
def _embed(table, tokens):
    return table[tokens].astype(F32)


def _head_gap(x, final_norm, head, nxt, eps):
    """Per position: the top logit minus the logit of ``nxt``.  A block of
    positions against a slice of the vocabulary's columns at a time."""
    s, vocab = x.shape[0], head.shape[1]
    size = min(POSITION_BLOCK, s)
    slices = VOCAB_SLICES if vocab % VOCAB_SLICES == 0 else 1
    width = vocab // slices
    head = jnp.moveaxis(head.reshape(head.shape[0], slices, width), 1, 0)
    x = _rms_norm(x, final_norm.astype(F32), eps)

    def block(args):
        xb, nb = args

        def part(carry, hw):
            top, own = carry
            cols, first = hw
            lg = xb @ cols.astype(F32)                      # (size, width)
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


# ----------------------------------------------------------------- model
def _kinds(config: Dict[str, Any]):
    """The kind of each of the ``num_hidden_layers`` layers."""
    for key, want in (("use_rope", False), ("use_gqa_gate", True),
                      ("kda_use_full_proj", False),
                      ("kda_allow_neg_eigval", True),
                      ("first_k_dense_replace", 0),
                      ("tie_word_embeddings", False)):
        if config.get(key, want) != want:
            raise ValueError(f"solar_open2_decoder: {key}={config[key]!r} "
                             f"is not modelled")
    attending = set(config["gqa_layers"])
    return ["attention" if i in attending else "kda"
            for i in range(config["num_hidden_layers"])]


def _layer_weights(layers, kinds, i: int):
    """Layer ``i``'s leaves, each counted among the layers that have it."""
    among = kinds[:i].count(kinds[i])
    out = {}
    for name, leaf in layers.items():
        mine = name in ATTENTION_LEAVES if kinds[i] == "attention" \
            else name.startswith("kda_")
        shared = name not in ATTENTION_LEAVES and not name.startswith("kda_")
        if mine or shared:
            out[name] = leaf[among if mine else i]
    return out


def _padded(tokens):
    """The row lengthened with zeros to whole blocks (what follows a
    position never reaches it)."""
    s = len(tokens)
    if s <= QUERY_BLOCK:
        return tokens
    return np.concatenate([tokens, np.zeros(-s % POSITION_BLOCK, np.int32)])


def _hidden(params, tokens, config):
    """For ONE row of tokens (S,): the last layer's output (S, D) and the
    experts each layer chose (L, S, k)."""
    kinds = _kinds(config)
    share = config.get("share", {})
    routing = (float(config["rms_norm_eps"]), share.get("experts_first", 0),
               config["num_experts_per_tok"], bool(config["norm_topk_prob"]),
               float(config["routed_scaling_factor"]))
    x = _embed_jit(params["embed_tokens"], jnp.asarray(tokens))
    chosen = []
    for i, kind in enumerate(kinds):
        w = _layer_weights(params["layers"], kinds, i)
        x = (_attention if kind == "attention" else _kda)(x, w, config)
        x, picked = _ffn_jit(
            x, {k: w[k] for k in ("mlp_norm", "router", "router_bias",
                                  "ws_gate", "ws_up", "ws_down")
                + EXPERT_STACKS}, *routing)
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
    """For a greedy decoder's ``emitted`` tokens after ``prompt``, one full
    forward pass over prompt + emitted.  Per emitted token, at the position
    that produced it: ``gap``, the reference's top logit minus the
    reference's logit of the token that was emitted (0 where they agree);
    and ``chosen`` (L, n, k), the experts each layer of the reference chose
    there.  ``pad_to`` lengthens the row with zeros to one compiled shape:
    the conv, the recurrence and the attention are causal, and an expert
    layer mixes no positions."""
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


def gap_counts(gap: np.ndarray) -> Dict[str, Any]:
    """What a request's gaps look like, for the record a run prints."""
    top = np.sort(gap)[::-1][:6]
    return {"positions": int(len(gap)), "max": float(gap.max()),
            "mean": float(gap.mean()),
            "over_0.03": int((gap > 0.03).sum()),
            "over_0.05": int((gap > 0.05).sum()),
            "over_0.1": int((gap > 0.1).sum()),
            "over_0.25": int((gap > 0.25).sum()),
            "top": [round(float(g), 4) for g in top]}


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """``teacher_forced_report``'s gap at each emitted position, with the
    near-tie swaps of a request taken out (``take_out_swaps``), and one
    ``reference_gaps`` line of what was read (for the record a run
    leaves)."""
    raw = teacher_forced_report(params, prompt, emitted, config,
                                pad_to)["gap"]
    gap = take_out_swaps(raw)
    print(json.dumps({"event": "reference_gaps", **gap_counts(raw),
                      "judged_max": float(gap.max())}), flush=True)
    return gap


# Over SWAP_GAP a position's gap is a swap of experts at a near-tie, not
# rounding; over SWAP_CEILING it is no swap either.
SWAP_GAP = 0.05
SWAP_CEILING = 1.0


def swaps_allowed(n: int) -> int:
    """Of a request's ``n`` emitted positions, how many may read over
    SWAP_GAP: an eighth and four more, which keeps a short request's count
    from deciding by chance."""
    return 4 + n // 8


def take_out_swaps(gap: np.ndarray) -> np.ndarray:
    """A request's gaps with those over SWAP_GAP set to zero, if they are at
    most ``swaps_allowed`` and none is over SWAP_CEILING; as they were read
    otherwise.

    Why a count.  The 8 of 320 experts a token takes are those with the
    largest sigmoid score + bias; under random weights the 8th and 9th lie
    closer than a bfloat16 stream's rounding moves a score for a few
    percent of (token, layer) pairs, the engine then takes the other expert
    and -- where that expert or the one it displaced is among the 40 held
    here -- adds or leaves out one expert's output, which the states and
    the K/V rows carry to the positions after.  One pick in eight lands on
    this chip, so the swaps that show are fewer than a whole layer's.
    Measured on the chip at the published widths (PERF.md section 6, PR 55:
    the cell's first 28 checked requests and tools/kda_check.py, 7,590
    positions): a sound engine's request reads 0-8.0% of its positions over
    0.05 (7 of 88, 17 of 311, 24 of 650; 4.0% typical of the long ones),
    the rest under 0.03 and mostly exactly 0 (the same token leads), its
    largest gap 0.49 (eight positions over 0.25);
    tests/test_solar_open2_serve.py holds every position in float32, where
    no tie breaks differently (1.5e-5 of a deviation in LOGITS).

    The limits, each between two readings.  An eighth of a request's
    positions (and four): between 8.0%, the most a sound request read (7 of
    88, where 15 are allowed), and 27%, what the mildest broken program
    reads (the decay applied after the
    correction, 70 of 256; weights in float8_e4m3's mantissa, the precision
    below, 51%; beta without its 2 61%; the attention gate dropped 98%).
    One position over 1.0: between 0.49, the largest gap any sound position
    read, and 3.8-4.4, what an arbitrary token reads (the dropped gate's
    positions; a stale state or cache row gives such tokens at a few
    positions only, which a count would let through).  NOT seen on the chip
    at all: the matrix state stored in bfloat16 reads as a sound engine
    does (10 of 256 over 0.05, largest 0.17); the CPU tests hold it, by the
    logits at every position."""
    swapped = gap > SWAP_GAP
    if (swapped.sum() > swaps_allowed(len(gap))
            or (gap > SWAP_CEILING).any()):
        return gap
    return np.where(swapped, 0.0, gap)

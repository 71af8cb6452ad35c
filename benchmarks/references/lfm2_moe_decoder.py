"""Plain reference of LFM2-8B-A1B's decoder (``model_type: lfm2_moe``: the
published ``config.json`` as the catalog beside the model-configs guide
holds it, and the public ``Lfm2Moe`` modeling code as known).  Every layer
is pre-norm, ``norm_eps`` 1e-5, no biases, no multipliers:

    x <- x + Mixer(RMSNorm(x; g_operator));  x <- x + FFN(RMSNorm(x; g_ffn))

Mixer, ``layer_types[l] == "conv"`` (gated short convolution):

    [B | C | X] = h W_in                   2048 -> 3 x 2048, in that order
    u   = B * X
    v_t = sum_{j<3} w_j * u_{t-2+j}        depthwise, causal, zeros before
                                           position 0, no bias, no activation
    out = (C * v) W_out

Mixer, ``"full_attention"``:

    q, k, v = h W_q, h W_k, h W_v          32 / 8 / 8 heads of 64
    q, k = RMSNorm over each head's 64 (one weight of 64, shared by heads)
    q, k = rotate-half RoPE(theta 1e6) over the whole head
    a_i = softmax_j(q_i . k_j / 8), j <= i;   out = concat(a) W_o

FFN, layers below ``num_dense_layers``: ``W_2 (silu(W_1 h) * W_3 h)``.
FFN, the others:

    s = sigmoid(h W_r)                     float32, all experts
    chosen = the num_experts_per_tok largest of s + b   (b: the CHOICE only)
    g = s[chosen] / (sum s[chosen] + 1e-6)     (norm_topk_prob) x
        routed_scaling_factor
    y = sum_{e in chosen} g_e W_2,e (silu(W_1,e h) * W_3,e h)

then a final RMSNorm and the head tied to the embedding.

Departures from the published description, each a reading listed in the
configuration file's ``assumed``: the chunk order ``[B | C | X]``; the
per-head q/k norm BEFORE RoPE; RoPE in the rotate-half convention; the
conv weight stored taps-major ``(3, 2048)``, oldest tap first;
``tie_word_embeddings`` true; the depth cut to ``num_hidden_layers`` of
the list (the file's ``reduced``).

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no conv
state, no sort, no grouped matmul.  The conv reads every position's ``u``
from the row itself; the experts are a LOOP over experts, each computed
on every token and weighted by a gate that is zero where it was not
chosen, so it cannot share a routing, state or cache bug with
``ray_tpu/``.  It shares nothing with ``ray_tpu/models/`` but the
parameter pytree's key names.  The tree holds the layers as the program
walks them, in PARTS: ``dense_layers`` (the leading dense layers) then
``layers``, ``layers_1``, ... (the expert layers, cut where the pattern
of kinds changes), each leaf stacked over the layers of its part that
have it, in the layers' order:

    embed_tokens (V, H); final_norm (H,); per part
    attn_norm, mlp_norm (n, H)                       every layer
    conv_in (nc, H, 3H), conv_w (nc, 3, H), conv_out (nc, H, H)    conv
    wq (na, H, Hq*D), wk, wv (na, H, Hkv*D), wo (na, Hq*D, H),
    q_norm, k_norm (na, D)                           attention
    w_gate, w_up (n, H, F), w_down (n, F, H)         dense parts
    router (n, H, E), router_bias (n, E),
    w_gate, w_up (n, E, H, Fe), w_down (n, E, Fe, H)  expert parts

so layer l's leaf is found by counting (``_locate``): nothing is copied
or concatenated.  The benchmark pads every checked row to the engine's
``max_len`` (512) and runs this beside the loaded engine: one layer is
one jitted call, an expert's weights are widened to float32 one at a
time inside it (44 MB), and the head runs a block of positions against a
slice of the vocabulary at a time, keeping only the top logit and the
next token's.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256
POSITION_BLOCK = 512
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
CONV_LEAVES = ("conv_in", "conv_w", "conv_out")
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


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


def _attention(h, w, heads, kv_heads, head_dim, theta, eps):
    """h (S, H) normed -> (S, H): a block of queries against every key."""
    s = h.shape[0]
    q = (h @ w["wq"]).reshape(s, heads, head_dim)
    k = (h @ w["wk"]).reshape(s, kv_heads, head_dim)
    v = (h @ w["wv"]).reshape(s, kv_heads, head_dim)
    q = _rope(_rms_norm(q, w["q_norm"], eps), theta)
    k = _rope(_rms_norm(k, w["k_norm"], eps), theta)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    size = min(QUERY_BLOCK, s)
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, i = args
        scores = jnp.einsum("qhd,khd->hqk", qb, k) / np.sqrt(head_dim)
        probs = jax.nn.softmax(
            jnp.where((j <= i[:, None])[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = jax.lax.map(block, (_blocks(q, size), _blocks(jnp.arange(s), size)))
    return out.reshape(s, heads * head_dim) @ w["wo"]


def _short_conv(h, w):
    """h (S, H) normed -> (S, H).  ``u`` at positions before 0 is zero."""
    d = h.shape[1]
    bcx = h @ w["conv_in"]
    u = bcx[:, :d] * bcx[:, 2 * d:]
    taps = w["conv_w"].shape[0]
    v = jnp.zeros_like(u)
    for j in range(taps):                     # w_j reads u_{t - (taps-1) + j}
        back = taps - 1 - j
        v = v + w["conv_w"][j] * jnp.pad(u, ((back, 0), (0, 0)))[:u.shape[0]]
    return (bcx[:, d:2 * d] * v) @ w["conv_out"]


def _swiglu(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def _route(h, router, bias, top_k, norm_topk, scale):
    """Gates (S, E), zero where an expert was not chosen, and the choice
    (S, k)."""
    scores = jax.nn.sigmoid(h @ router)
    _, chosen = jax.lax.top_k(scores if bias is None else scores + bias,
                              top_k)
    member = jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32).sum(-2)
    gates = scores * member
    if norm_topk:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-6)
    return gates * scale, chosen


def _experts(h, gates, w_gate, w_up, w_down):
    """Every expert on every token, one expert after another, each
    widened to float32 where it is used."""
    def one(y, ws):
        wg, wu, wd, g = ws
        return y + g[:, None] * _swiglu(h, wg.astype(F32), wu.astype(F32),
                                        wd.astype(F32)), None

    return jax.lax.scan(one, jnp.zeros_like(h),
                        (w_gate, w_up, w_down, gates.T))[0]


def _layer(x, w, kind, heads, kv_heads, head_dim, theta, eps, top_k,
           norm_topk, scale):
    """x (S, H) float32; w: the layer's weights as stored.  ``top_k`` 0: a
    dense layer.  Returns (x, the experts chosen (S, k) or None)."""
    stacks = {k: w[k] for k in EXPERT_STACKS}
    w = {k: v.astype(F32) for k, v in w.items() if k not in stacks}
    h = _rms_norm(x, w["attn_norm"], eps)
    if kind == "conv":
        x = x + _short_conv(h, w)
    else:
        x = x + _attention(h, w, heads, kv_heads, head_dim, theta, eps)
    h = _rms_norm(x, w["mlp_norm"], eps)
    if not top_k:
        return x + _swiglu(h, *(stacks[k].astype(F32)
                                for k in EXPERT_STACKS)), None
    gates, chosen = _route(h, w["router"], w.get("router_bias"), top_k,
                           norm_topk, scale)
    return x + _experts(h, gates, **stacks), chosen


# static: everything after the weights
_layer_jit = jax.jit(_layer, static_argnums=tuple(range(2, 11)))


def _embed(table, tokens):
    return table[tokens].astype(F32)


def _head_gap(x, final_norm, table, nxt, eps):
    """Per position: the top logit minus the logit of ``nxt``, the head
    the embedding transposed.  A block of positions against a slice of
    the vocabulary's rows at a time."""
    s, vocab = x.shape[0], table.shape[0]
    size = min(POSITION_BLOCK, s)
    slices = 8 if vocab % 8 == 0 and vocab > 32768 else 1
    width = vocab // slices
    table = table.reshape(slices, width, table.shape[1])
    x = _rms_norm(x, final_norm.astype(F32), eps)

    def block(args):
        xb, nb = args

        def part(carry, tw):
            top, own = carry
            rows, first = tw
            lg = xb @ rows.astype(F32).T                   # (size, width)
            at = jnp.clip(nb - first, 0, width - 1)
            mine = jnp.take_along_axis(lg, at[:, None], -1)[:, 0]
            inside = (nb >= first) & (nb < first + width)
            return (jnp.maximum(top, lg.max(-1)),
                    jnp.where(inside, mine, own)), None

        (top, own), _ = jax.lax.scan(
            part, (jnp.full((size,), -jnp.inf, F32), jnp.zeros((size,), F32)),
            (table, jnp.arange(slices) * width))
        return top - own

    return jax.lax.map(block, (_blocks(x, size), _blocks(nxt, size))
                       ).reshape(s)


def _head(x, final_norm, table, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ table.astype(F32).T


_embed_jit = jax.jit(_embed)
_head_jit = jax.jit(_head, static_argnums=(3,))
_head_gap_jit = jax.jit(_head_gap, static_argnums=(4,))


def _kinds(config: Dict[str, Any]):
    """The kind of each of the ``num_hidden_layers`` layers."""
    for key, want in (("conv_bias", False), ("use_expert_bias", True),
                      ("tie_word_embeddings", True)):
        if config.get(key, want) is not want:
            raise ValueError(f"lfm2_moe_decoder: {key}={config[key]!r} is "
                             f"not modelled")
    kinds = list(config["layer_types"])
    if len(kinds) != config["num_hidden_layers"] \
            or set(kinds) - {"conv", "full_attention"}:
        raise ValueError("lfm2_moe_decoder: layer_types names each layer "
                         "conv or full_attention")
    return kinds


def _locate(params, key_prefix: str, name: str, index: int):
    """The ``index``-th of the layers that have leaf ``name`` among the
    parts whose key starts with ``key_prefix``, in the parts' order."""
    part_keys = sorted(
        (k for k in params if k == key_prefix
         or k.startswith(key_prefix + "_") and k[len(key_prefix) + 1:]
         .isdigit()),
        key=lambda k: int(k[len(key_prefix) + 1:] or 0))
    for key in part_keys:
        leaf = params[key].get(name)
        if leaf is None:
            continue
        if index < leaf.shape[0]:
            return leaf[index]
        index -= leaf.shape[0]
    raise ValueError(f"lfm2_moe_decoder: no layer {index} of {name} under "
                     f"{key_prefix}")


def _layer_weights(params, kinds, dense_layers: int, i: int):
    """Layer ``i``'s leaves: the dense layers under ``dense_layers*``,
    the others under ``layers*``, each leaf counted among the layers of
    its part group that have it."""
    dense = i < dense_layers
    prefix, first = ("dense_layers", 0) if dense else ("layers",
                                                       dense_layers)
    among = kinds[first:i]
    mixer = CONV_LEAVES if kinds[i] == "conv" else ATTENTION_LEAVES
    names = {name: i - first for name in ("attn_norm", "mlp_norm")
             + EXPERT_STACKS + (() if dense else ("router", "router_bias"))}
    names.update({name: among.count(kinds[i]) for name in mixer})
    return {name: _locate(params, prefix, name, at)
            for name, at in names.items()}


def _padded(tokens):
    """The row lengthened with zeros to whole blocks (what follows a
    position never reaches it)."""
    s = len(tokens)
    if s <= QUERY_BLOCK:
        return tokens
    return np.concatenate([tokens, np.zeros(-s % POSITION_BLOCK, np.int32)])


def _hidden(params, tokens, config):
    """For ONE row of tokens (S,): the last layer's output (S, H) and
    the experts each expert layer chose (Le, S, k)."""
    kinds = _kinds(config)
    dense_layers = config["num_dense_layers"]
    sizes = (config["num_attention_heads"], config["num_key_value_heads"],
             config["hidden_size"] // config["num_attention_heads"],
             float(config["rope_theta"]), float(config["norm_eps"]))
    x = _embed_jit(params["embed_tokens"], jnp.asarray(tokens))
    chosen = []
    for i, kind in enumerate(kinds):
        routed = i >= dense_layers
        x, picked = _layer_jit(
            x, _layer_weights(params, kinds, dense_layers, i), kind, *sizes,
            config["num_experts_per_tok"] if routed else 0,
            bool(config["norm_topk_prob"]),
            float(config["routed_scaling_factor"]))
        if routed:
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
                      params["final_norm"], params["embed_tokens"],
                      float(config["norm_eps"]))
            for row in tokens])


def teacher_forced_report(params: Dict[str, Any], prompt, emitted,
                          config: Dict[str, Any], pad_to: int = 0):
    """For a greedy decoder's ``emitted`` tokens after ``prompt``, one
    full forward pass over prompt + emitted.  Per emitted token, at the
    position that produced it: ``gap``, the reference's top logit minus
    the reference's logit of the token that was emitted (0 where they
    agree); and ``chosen`` (Le, n, k), the experts each expert layer of
    the reference chose there.  ``pad_to`` lengthens the row with zeros
    to one compiled shape: a causal conv and causal attention keep what
    follows a position from reaching it, and an expert layer mixes no
    positions."""
    seq = list(prompt) + list(emitted)
    seq = _padded(np.asarray(seq + [0] * max(0, pad_to - len(seq)),
                             np.int32))
    at = slice(len(prompt) - 1, len(prompt) - 1 + len(emitted))
    with jax.default_matmul_precision("highest"):
        x, chosen = _hidden(params, seq, config)
        gap = np.asarray(_head_gap_jit(
            x, params["final_norm"], params["embed_tokens"],
            jnp.asarray(np.roll(seq, -1)), float(config["norm_eps"])))
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
    import json

    raw = teacher_forced_report(params, prompt, emitted, config,
                                pad_to)["gap"]
    gap = take_out_swaps(raw)
    print(json.dumps({"event": "reference_gaps", **gap_counts(raw),
                      "judged_max": float(gap.max())}), flush=True)
    return gap


# Over SWAP_GAP a position's gap is a swap of experts at a near-tie, not
# rounding; over SWAP_CEILING it is no swap either.
SWAP_GAP = 0.05
SWAP_CEILING = 2.5
WILD_ALLOWED = 1


def swaps_allowed(n: int) -> int:
    """Of a request's ``n`` emitted positions, how many may read over
    SWAP_GAP: 55% and eight more, which keeps a short request's count from
    deciding by chance."""
    return 8 + 11 * n // 20


def take_out_swaps(gap: np.ndarray) -> np.ndarray:
    """A request's gaps with those over SWAP_GAP set to zero, if they are
    at most ``swaps_allowed`` and at most WILD_ALLOWED of them are over
    SWAP_CEILING; as they were read otherwise.

    Why a count.  The 4 of 32 experts a token takes are those with the
    largest sigmoid score + bias, the 4th and 5th of which lie 0.019
    apart at the median under random weights, and each chosen expert
    carries about a quarter of the layer's FFN.  A bfloat16 engine's
    stream is ~0.4% off this float32 reference's by the middle of the
    stack, which moves a score by ~0.001: at some 4% of (token, layer)
    pairs the engine takes the other expert, adds ANOTHER random expert's
    output, and the conv state and K/V carry that to the positions after.
    Measured on the chip at the published widths (PERF.md section 6,
    PR 40; 66 requests of 14 runs and of tools/lfm2_check.py, 12,109
    positions): a sound engine's request reads 25.0-46.7% of its positions
    over 0.05 (37% typical), the rest mostly exactly 0 (the same token
    leads), its largest gap 2.14 (one position over 2.0) and its mean gap
    0.06-0.13; tests/test_lfm2_serve.py holds every position in float32,
    where no tie breaks differently (gap 0.0).

    The limits, each between two readings.  55% of a request's positions:
    between 46.7%, the most a sound request read (63 of 135), and 71%,
    what the mildest broken program caught by count reads (the bias left
    out of the choice, 182 of 256; weights in float8_e4m3's mantissa, the
    precision below, 86-90%; the conv's oldest tap dropped 99.6%).  More
    than one position over 2.5: no sound request read any (largest 2.14);
    a conv state left from the tenant before reads 1, 3, 5 and 4 such
    positions in four requests and a state advanced on an idle slot 4, 3,
    4 and 5 (gaps to 5.9: an arbitrary token), so seven of those eight
    readings are judged not correct and one passes.  NOT seen on the chip
    at all: the bias left in the gates (a 2-3% change of a gate: 39% loud,
    mean 0.137) and the q/k norm over the whole projection (44%, mean
    0.154) read as a sound engine does; the CPU tests hold both."""
    swapped = gap > SWAP_GAP
    if (swapped.sum() > swaps_allowed(len(gap))
            or (gap > SWAP_CEILING).sum() > WILD_ALLOWED):
        return gap
    return np.where(swapped, 0.0, gap)

"""Plain reference of DeepSeek-V2's decoder (arXiv:2405.04434; the
published ``config.json`` as the catalog beside the model-configs guide
holds it, the public modeling code as known), EXPANDED form only.  For a
layer with input x (pre-norm RMSNorm, no biases):

    h     = RMSNorm(x; g_attn)
    c_q   = RMSNorm(h W_DQ; g_q)                       q_lora_rank
    [q_nope_i ; q_rope_i] = c_q W_UQ      per head i   128 + 64
    [c_kv ; k_r] = h W_DKV                             kv_lora_rank + 64
    c_kv  = RMSNorm(c_kv; g_kv);  k_rope = RoPE(k_r)   ONE per token
    [k_nope_i ; v_i] = c_kv W_UKV         per head i   128 + 128
    score_ij = s (q_nope_i . k_nope_j + RoPE(q_rope_i) . k_rope_j), j <= i
    x'    = x + concat_i(softmax_j(score_ij) v_j) W_O

    RoPE: on the 64-wide parts only, pairs taken interleaved (2i, 2i+1),
    YaRN frequencies (``_yarn``); s = (128 + 64)^-1/2 m^2 with m =
    0.1 mscale_all_dim ln(factor) + 1.

    h'    = RMSNorm(x'; g_mlp)
    layer < first_k_dense_replace:  out = x' + SwiGLU_12288(h')
    else:  p = softmax(h' W_r) in float32 over ALL n routed experts;
           the experts are n_group groups of consecutive ones, a group
           scores its largest p, the topk_group best groups stay and the
           others' p count as 0; T = the num_experts_per_tok largest of
           what stays; gates p_e as they are (norm_topk_prob false) x
           routed_scaling_factor
           out = x' + S(h') + sum_{e in T, e HELD} gate_e E_e(h')

then a final RMSNorm and the untied output head.  S is the shared expert,
one SwiGLU of width n_shared_experts x moe_intermediate_size.

THE SHARE (configs/deepseek-v2.json ``share``): this chip is one of the
chips that share each layer and holds experts ``experts_first ..
experts_first + n_routed_experts`` of ``n_routed_experts_published``.  The
router scores all of them; the sum runs over the chosen experts that are
HELD, and what the others would add is left out -- here exactly as in the
program.  The vocabulary is the slice the file's ``vocab_size`` says.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no absorption, no latent cache,
no kernel, no sort; a Python loop over layers and over groups of heads,
every HELD expert on every token under a gate that is zero outside the
chosen ones.  It shares nothing with ``ray_tpu/models/`` but the parameter
pytree's key names:

    embed_tokens (V, D); final_norm (D,); lm_head (D, V);
    dense_layers.{...} the first_k_dense_replace leading layers and
    layers.{...} the expert layers, each stacked over its own count:
      attn_norm, mlp_norm (L, D); wq_a (L, D, Rq); q_a_norm (L, Rq);
      wq_b (L, Rq, H*(nope+rope)), a head's columns nope then rope;
      wkv_a (L, D, R+rope), columns c_kv then k_r; kv_a_norm (L, R);
      wk_b (L, H, nope, R) = W_UK a head, transposed; wv_b (L, H, R, v) =
      W_UV a head; wo (L, H*v, D);
      dense: w_gate, w_up (L, D, F), w_down (L, F, D);
      expert: router (L, D, n_published); w_gate, w_up (L, held, D, Fe),
      w_down (L, held, Fe, D); ws_gate, ws_up (L, D, Fs), ws_down.

The benchmark pads every checked row to the engine's ``max_len`` (16,384)
and runs this BESIDE the loaded engine (10.3 GB of weights and its cache:
some 2 GB are free), so the same mathematics runs in blocks, each a plain
product, and no matrix is widened to float32 but the slice in use: a
group of heads at a time and a block of queries at a time against every
key; positions a block at a time through an FFN, its width a slice (or an
expert) at a time; the head a block of positions and a slice of the
vocabulary at a time, keeping only the top logit and the next token's.
"""

from __future__ import annotations

import json
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HEAD_GROUP = 8           # heads attended in one call
QUERY_BLOCK = 256        # 8 x 256 x 16,384 float32 scores: 134 MB
POSITION_BLOCK = 1024    # through an FFN / the head
VOCAB_SLICES = 4
# Over this a position's gap is a swap of experts at a near-tie, not
# rounding: a sound engine's other positions read under 0.03, the mildest
# broken program's MEAN gap is 0.075 (PERF.md section 6, PR 37); the
# harness's margin is 0.25.
SWAP_GAP = 0.05


def swaps_allowed(n: int) -> int:
    """Of a request's ``n`` emitted positions, how many may read over
    SWAP_GAP: 17% (a sound engine's requests read 1.3-9.0%; the latent
    rows in float8's mantissa 25.8%, no group limit 49.6%, every other
    broken program 77-100%) and eight more, which keeps a short request's
    count from deciding by chance."""
    return 8 + 17 * n // 100


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _yarn(dim: int, theta: float, scaling):
    """``(inv_freq (dim/2,), factor on cos and sin, m)``: YaRN as the
    public code computes it.  ``f_extra = theta^(-2i/dim)``, ``f_inter =
    f_extra / factor``; pair i takes ``f_inter (1 - k_i) + f_extra k_i``
    with ``k_i = 1 - clip((i - low) / (high - low), 0, 1)``, low / high the
    pairs that turn beta_fast / beta_slow times over the original context
    (floor / ceil).  ``m = 0.1 mscale_all_dim ln(factor) + 1`` scales the
    scores by its square."""
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2 * i / dim)
    if scaling is None:
        return extra, 1.0, 1.0
    factor, original = scaling["factor"], \
        scaling["original_max_position_embeddings"]

    def pair(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(pair(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair(scaling["beta_slow"])), dim - 1)
    keep = 1 - np.clip((i - low) / (high - low), 0, 1)
    mscale = lambda m: 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1
    return (extra / factor * (1 - keep) + extra * keep,
            mscale(scaling["mscale"]) / mscale(scaling["mscale_all_dim"]),
            mscale(scaling["mscale_all_dim"]))


def _rope(x, inv_freq, factor):
    """x (S, heads, rope), positions 0..S-1; the pairs are (2i, 2i+1).
    The result lists every pair's first then every pair's second, for
    queries and keys alike: their dot product does not see the order."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = (jnp.cos(ang) * factor)[:, None], (jnp.sin(ang) * factor)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(x, size):
    """(S, ...) -> (S / size, size, ...)."""
    return x.reshape((x.shape[0] // size, size) + x.shape[1:])


def _latents(x, w, eps, rank, inv_freq, factor):
    """x (S, D) -> c_q (S, Rq), c_kv (S, R) normed, k_rope (S, rope)
    roped: what every head's queries, keys and values come up from."""
    w = {k: v.astype(F32) for k, v in w.items()}
    h = _rms_norm(x, w["attn_norm"], eps)
    cq = _rms_norm(h @ w["wq_a"], w["q_a_norm"], eps)
    kv = h @ w["wkv_a"]
    ckv = _rms_norm(kv[:, :rank], w["kv_a_norm"], eps)
    return cq, ckv, _rope(kv[:, None, rank:], inv_freq, factor)[:, 0]


def _attend_heads(x, cq, ckv, k_rope, wq_b, wk_b, wv_b, wo, nope, inv_freq,
                  factor, scale):
    """``x + concat_i(attention of head i) W_O,i`` for ONE group of heads:
    wq_b (Rq, G, nope + rope), wk_b (G, nope, R), wv_b (G, R, v), wo (G,
    v, D).  Expanded: the group's keys and values at every position, a
    block of queries at a time against all of them, masked."""
    s = x.shape[0]
    q = jnp.einsum("sr,rgd->sgd", cq, wq_b.astype(F32))
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], inv_freq, factor)
    k_nope = jnp.einsum("sc,gdc->sgd", ckv, wk_b.astype(F32))
    v = jnp.einsum("sc,gcd->sgd", ckv, wv_b.astype(F32))
    size = min(QUERY_BLOCK, s)
    j = jnp.arange(s)[None, :]

    def block(args):
        qn, qr, i = args
        scores = (jnp.einsum("qgd,kgd->gqk", qn, k_nope)
                  + jnp.einsum("qgd,kd->gqk", qr, k_rope)) * scale
        probs = jax.nn.softmax(
            jnp.where((j <= i[:, None])[None], scores, -jnp.inf), -1)
        return jnp.einsum("gqk,kgd->qgd", probs, v)

    out = jax.lax.map(block, (_blocks(q_nope, size), _blocks(q_rope, size),
                              _blocks(jnp.arange(s), size)))
    return x + jnp.einsum("sgd,gdh->sh", out.reshape(v.shape),
                          wo.astype(F32))


def _gates(p, groups, top_groups, top_k, scaling_factor, norm_topk):
    """p (S, n) router probabilities -> gates (S, n), zero outside the
    chosen experts: group-limited greedy."""
    s, n = p.shape
    best = p.reshape(s, groups, n // groups).max(-1)
    _, kept = jax.lax.top_k(best, top_groups)
    stays = jax.nn.one_hot(kept, groups, dtype=F32).sum(1)        # (S, G)
    p_kept = p * jnp.repeat(stays, n // groups, axis=1)
    _, chosen = jax.lax.top_k(p_kept, top_k)
    gates = p_kept * jax.nn.one_hot(chosen, n, dtype=F32).sum(1)
    if norm_topk:
        gates = gates / gates.sum(-1, keepdims=True)
    return gates * scaling_factor, chosen


def _ffn(x, w, li, eps, dense, first, groups, top_groups, top_k,
         scaling_factor, norm_topk, width):
    """The FFN half of layer ``li`` of the stacks ``w`` (as stored, whole:
    a layer's 40 experts are 1.9 GB and are never copied out), x (S, D) ->
    (S, D), a block of positions at a time.  Every SwiGLU is summed a
    slice of its width at a time, each slice widened to float32 alone: a
    dense layer's FFN and the shared expert as slices of ``width`` under a
    gate of 1, the held experts each under its own gate."""
    s, d = x.shape
    size = min(POSITION_BLOCK, s)
    norm = w["mlp_norm"][li].astype(F32)

    def columns(gate, up, down):
        """Slice e of a (L, D, F) SwiGLU's width."""
        def get(e):
            cut = lambda m, at, shape: jax.lax.dynamic_slice(
                m, (li,) + at, (1,) + shape)[0].astype(F32)
            return (cut(w[gate], (0, e * width), (d, width)),
                    cut(w[up], (0, e * width), (d, width)),
                    cut(w[down], (e * width, 0), (width, d)))

        return get, w[gate].shape[2] // width

    def expert(e):
        """Held expert e of the (L, held, D, Fe) stacks."""
        return tuple(w[k][li, e].astype(F32)
                     for k in ("w_gate", "w_up", "w_down"))

    def swiglus(y, h, get, gates):
        """y + sum_e gates[:, e] * SwiGLU_e(h), one at a time."""
        def one(y, eg):
            e, g = eg
            wg, wu, wd = get(e)
            act = jax.nn.silu(h @ wg) * (h @ wu)
            return y + (act * g[:, None]) @ wd, None

        return jax.lax.scan(
            one, y, (jnp.arange(gates.shape[1]), gates.T))[0]

    def block(xb):
        h = _rms_norm(xb, norm, eps)
        if dense:
            get, n = columns("w_gate", "w_up", "w_down")
            return swiglus(xb, h, get, jnp.ones((size, n), F32))
        p = jax.nn.softmax(h @ w["router"][li].astype(F32), axis=-1)
        gates, _ = _gates(p, groups, top_groups, top_k, scaling_factor,
                          norm_topk)
        held = w["w_gate"].shape[1]
        y = swiglus(xb, h, expert, gates[:, first:first + held])
        get, n = columns("ws_gate", "ws_up", "ws_down")
        return swiglus(y, h, get, jnp.ones((size, n), F32))

    return jax.lax.map(block, _blocks(x, size)).reshape(x.shape)


_latents_jit = jax.jit(_latents, static_argnums=(2, 3, 5))
_attend_jit = jax.jit(_attend_heads, static_argnums=(8, 10, 11),
                      donate_argnums=(0,))
_ffn_jit = jax.jit(_ffn, static_argnums=tuple(range(3, 12)),
                   donate_argnums=(0,))


def _embed(table, tokens):
    return table[tokens].astype(F32)


def _head_gap(x, final_norm, head, nxt, eps):
    """Per position: the top logit minus the logit of ``nxt``.  A block
    of positions against a slice of the vocabulary at a time."""
    s, vocab = x.shape[0], head.shape[1]
    size = min(POSITION_BLOCK, s)
    slices = VOCAB_SLICES if vocab % VOCAB_SLICES == 0 and vocab > 8192 else 1
    width = vocab // slices
    head = head.reshape(head.shape[0], slices, width).transpose(1, 0, 2)
    norm = final_norm.astype(F32)

    def block(args):
        xb, nb = args
        xb = _rms_norm(xb, norm, eps)

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


def _check(config: Dict[str, Any]):
    scaling = config.get("rope_scaling")
    if scaling is not None and scaling.get("type") != "yarn":
        raise ValueError("deepseek_v2_decoder: rope_scaling is YaRN or null")
    if config.get("scoring_func", "softmax") != "softmax":
        raise ValueError("deepseek_v2_decoder: a sigmoid router is not "
                         "modelled")
    if config.get("topk_method") != "group_limited_greedy":
        raise ValueError("deepseek_v2_decoder: group_limited_greedy routing")
    if config.get("moe_layer_freq", 1) != 1 or config["tie_word_embeddings"]:
        raise ValueError("deepseek_v2_decoder: every layer after the dense "
                         "ones has experts; the head is untied")
    if config.get("q_lora_rank") is None:
        raise ValueError("deepseek_v2_decoder: queries are compressed")


def _padded(tokens):
    """The row lengthened with zeros to whole blocks (what follows a
    position never reaches it)."""
    s = len(tokens)
    if s <= QUERY_BLOCK:
        return tokens
    return np.concatenate([tokens, np.zeros(-s % POSITION_BLOCK, np.int32)])


def _hidden(params, tokens, config):
    """For ONE row of tokens (S,): the last layer's output (S, D)."""
    _check(config)
    heads, nope = config["num_attention_heads"], config["qk_nope_head_dim"]
    rope, vdim = config["qk_rope_head_dim"], config["v_head_dim"]
    rank, eps = config["kv_lora_rank"], float(config["rms_norm_eps"])
    inv_freq, factor, m = _yarn(rope, float(config["rope_theta"]),
                                config.get("rope_scaling"))
    inv_freq = jnp.asarray(inv_freq, F32)
    scale = float((nope + rope) ** -0.5 * m * m)
    share = config.get("share", {})
    width = config["moe_intermediate_size"]
    routing = (share.get("experts_first", 0), config["n_group"],
               config["topk_group"], config["num_experts_per_tok"],
               float(config["routed_scaling_factor"]),
               bool(config["norm_topk_prob"]), width)
    attn_names = ("attn_norm", "wq_a", "q_a_norm", "wkv_a", "kv_a_norm")
    x = _embed_jit(params["embed_tokens"], jnp.asarray(tokens))
    leading = config["first_k_dense_replace"]
    group = min(HEAD_GROUP, heads)
    ffn_names = ("mlp_norm", "router", "w_gate", "w_up", "w_down",
                 "ws_gate", "ws_up", "ws_down")
    for i in range(config["num_hidden_layers"]):
        dense = i < leading
        stack = params["dense_layers"] if dense else params["layers"]
        li = i if dense else i - leading
        cq, ckv, k_rope = _latents_jit(
            x, {k: stack[k][li] for k in attn_names}, eps, rank, inv_freq,
            factor)
        wq_b = stack["wq_b"][li].reshape(-1, heads, nope + rope)
        wo = stack["wo"][li].reshape(heads, vdim, -1)
        for g in range(0, heads, group):
            at = slice(g, g + group)
            x = _attend_jit(x, cq, ckv, k_rope, wq_b[:, at],
                            stack["wk_b"][li, at], stack["wv_b"][li, at],
                            wo[at], nope, inv_freq, factor, scale)
        x = _ffn_jit(x, {k: stack[k] for k in ffn_names if k in stack},
                     jnp.int32(li), eps, dense, *routing)
    return x


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any]):
    """(B, S, V) float32 logits for ``tokens`` (B, S) int32.  ``config``
    is the configuration file's dict (published key names).  The whole
    (sliced) vocabulary at every position: for short rows."""
    tokens = np.asarray(tokens, np.int32)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _head_jit(_hidden(params, _padded(row), config)[:len(row)],
                      params["final_norm"], params["lm_head"],
                      float(config["rms_norm_eps"]))
            for row in tokens])


def teacher_forced_report(params: Dict[str, Any], prompt, emitted,
                          config: Dict[str, Any], pad_to: int = 0):
    """For a greedy decoder's ``emitted`` tokens after ``prompt``: at each
    emitted position, the reference's top logit minus the reference's
    logit of the token that was emitted (0 where they agree), one full
    forward pass over prompt + emitted.  ``pad_to`` lengthens the row
    with zeros to one compiled shape: causal attention keeps what follows
    a position from reaching it, and an FFN mixes no positions."""
    seq = list(prompt) + list(emitted)
    seq = _padded(np.asarray(seq + [0] * max(0, pad_to - len(seq)),
                             np.int32))
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, seq, config)
        gap = np.asarray(_head_gap_jit(
            x, params["final_norm"], params["lm_head"],
            jnp.asarray(np.roll(seq, -1)), float(config["rms_norm_eps"])))
    return {"gap": gap[len(prompt) - 1:len(prompt) - 1 + len(emitted)]}


def gap_counts(gap: np.ndarray) -> Dict[str, Any]:
    """What a request's gaps look like, for the record a run prints."""
    top = np.sort(gap)[::-1][:6]
    return {"positions": int(len(gap)), "max": float(gap.max()),
            "mean": float(gap.mean()),
            "over_0.05": int((gap > 0.05).sum()),
            "over_0.1": int((gap > 0.1).sum()),
            "over_0.25": int((gap > 0.25).sum()),
            "top": [round(float(g), 4) for g in top]}


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """``teacher_forced_report``'s gap at each emitted position, with the
    near-tie swaps of a request taken out, and one ``reference_gaps`` line
    of what was read (for the record a run leaves).

    Top-6 of 160 near-uniform probabilities among the 3 best of 8 groups
    is a near-tie somewhere in the stack for some tokens; an engine whose
    stream is bfloat16 breaks some of them the other way than this float32
    pass, and then adds ANOTHER expert's output under a gate that is x 16
    and not renormalised (OLMoE's gates, a twentieth of these, read 0.06
    at the largest).  Measured on the chip at the published widths
    (PERF.md section 6, PR 37): a sound engine's positions read under 0.03
    but for 1.3-9.0% of a request's, which read up to 2.07; the same
    program with its latent rows in float8's mantissa reads 25.8% of
    positions over 0.05, without the group limit 49.6%, and 77-100% for
    the other broken ones.  The reference cannot know which side the
    engine took, so a request may hold up to ``swaps_allowed(n)``
    positions over SWAP_GAP, which are then set to zero; a request with
    more is given back as it was read and fails the harness's margin by
    its raw gaps."""
    gap = teacher_forced_report(params, prompt, emitted, config,
                                pad_to)["gap"]
    judged = take_out_swaps(gap)
    print(json.dumps({"event": "reference_gaps", **gap_counts(gap),
                      "allowed": swaps_allowed(len(gap)),
                      "judged_max": float(judged.max())}), flush=True)
    return judged


def take_out_swaps(gap: np.ndarray) -> np.ndarray:
    """A request's gaps with those over SWAP_GAP set to zero, if they are
    at most ``swaps_allowed``; as they were read if they are more."""
    swapped = gap > SWAP_GAP
    if swapped.sum() > swaps_allowed(len(gap)):
        return gap
    return np.where(swapped, 0.0, gap)

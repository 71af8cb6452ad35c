"""Plain reference of NVIDIA-Nemotron-3-Super-120B-A12B's decoder
(``model_type: nemotron_h``: the published ``config.json`` as the catalog
beside the model-configs guide holds it; the layer equations as Nemotron-H,
arXiv:2504.03624, Mamba-2, arXiv:2405.21060, NVIDIA's Nemotron 3 white paper
(LatentMoE) and DeepSeek-V3, arXiv:2412.19437 (the router) write them, each
reading listed in the configuration file's ``assumed``).

The stack is ``hybrid_override_pattern``, one character a BLOCK, and every
block holds ONE sub-layer behind one pre-norm, no bias but the conv's:

    x <- x + f(RMSNorm(x; g))        f by the block's character

``M``, a Mamba-2 mixer (nh = 128 heads of hd = 64, G = 8 groups of N = 128
state dimensions, head j reading group j // (nh / G)):

    [z | xBC | dt] = h W_in                  (nh hd | nh hd + 2 G N | nh)
    xBC = silu(conv4(xBC) + b)               causal, depthwise, zeros
                                             before position 0
    x, B, C = split(xBC)                     B, C: (G, N)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)            a head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t             (hd, N) a head
    y_t = S_t C_t + D x_t
    out = (RMSNorm_group(y * silu(z)) * w) W_out     the norm over each
                                             GROUP's nh hd / G channels

``*``, softmax attention, GQA 32 / 2 heads of 128, NO positional rotation,
scale 128^-1/2:

    q, k, v = h W_q, h W_k, h W_v;  a_i = softmax_j(q_i . k_j / sqrt d), j <= i
    out = concat(a) W_o

``E``, a LatentMoE feed-forward part:

    s = sigmoid(h W_r)                       float32, all 512 experts
    chosen = the 22 largest of s + bias      (the bias in the CHOICE only;
                                             n_group 1: no group limit)
    gate = s[chosen] / (sum s[chosen] + 1e-6) x routed_scaling_factor
    u = h W_1                                4,096 -> 1,024 (moe_latent_size)
    E_e(u) = relu(u W_up_e)^2 W_down_e       TWO matrices, no gate (relu2)
    f = (sum_{e chosen} gate_e E_e(u)) W_2 + relu(h W_su)^2 W_sd

then a final RMSNorm and the untied output head.  The drafting head
(``num_nextn_predict_layers``) stands beside the model and is not modelled:
the model's own logits do not pass through it.

THE SHARE (configs/nemotron-3-super-120b-a12b.json ``share``): this chip is
one of the chips that share each layer and holds experts ``experts_first ..
experts_first + experts_held`` of the published ``n_routed_experts``.  The
router scores all of them; the sum runs over the chosen experts that are
HELD, and what the others would add is left out -- in the program and here
alike.  ``W_2`` is linear, so the shares' routed parts add up through it.

Departures from the published description, each also under ``assumed``: the
gates' denominator carries the program's ``+ 1e-6`` (DeepSeek-V3's public
code adds 1e-20; at 22 sigmoid scores the sum is ~11 and the difference is
below float32's rounding); ``time_step_min / max / floor`` are read as the
initialiser's range for ``dt_bias`` and no clamp is applied to ``dt``.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no state
handed on, no chunked form, no sort, no grouped matmul.  The recurrence is
ONE POSITION AFTER ANOTHER by ``lax.scan``, a group of heads at a time; the
attention a full causal softmax, a block of queries against every key; the
experts a LOOP over the held range, each computed on every token under a
gate that is zero where it was not chosen.  It shares nothing with
``ray_tpu/models/`` but the parameter tree's key names.  The program keeps a
mixer and the ``E`` right after it as one layer of its walk and cuts the
stack into parts (``layers``, ``layers_1``, ...); here a leaf is found by
its NAME alone, the i-th block that has it taking the i-th row over the
parts in their order:

    embed_tokens (V, D); lm_head (D, V); final_norm (D,); under layers*:
    attn_norm (D,)                              every M and * block's norm
    ssm_in (D, nh hd + conv_dim) columns [z | x | B | C]; ssm_dt (D, nh);
    ssm_conv_w (4, conv_dim) oldest tap first; ssm_conv_b (conv_dim,);
    ssm_dt_bias, ssm_A_log, ssm_D (nh,); ssm_norm (nh hd,);
    ssm_out (nh hd, D)                                         M blocks
    wq (D, Hq d); wk, wv (D, Hkv d); wo (Hq d, D)              * blocks
    mlp_norm (D,); router (D, E); router_bias (E,); w_lat_in (D, R);
    w_up (held, R, F); w_down (held, F, R); w_lat_out (R, D);
    ws_up (D, Fs); ws_down (Fs, D)                             E blocks

The benchmark pads every checked row to the engine's ``max_len`` (4,096)
and runs this beside the loaded engine, so a block is a few jitted calls
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
QUERY_BLOCK = 256        # 16 heads x 256 x 4,096 float32 scores: 67 MB
POSITION_BLOCK = 1024
VOCAB_SLICES = 4
MAMBA_LEAVES = ("ssm_in", "ssm_dt", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
                "ssm_A_log", "ssm_D", "ssm_norm", "ssm_out")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo")
EXPERT_LEAVES = ("mlp_norm", "router", "router_bias", "w_lat_in", "w_up",
                 "w_down", "w_lat_out", "ws_up", "ws_down")


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _blocks(x, size):
    """(S, ...) -> (S / size, size, ...)."""
    return x.reshape((x.shape[0] // size, size) + x.shape[1:])


# ---------------------------------------------------------------- mixers
def _mamba_group(h, w_z, w_x, w_b, w_c, w_dt, conv_x, conv_b, conv_c,
                 bias_x, bias_b, bias_c, dt_bias, a_log, d_skip, norm_w,
                 keep, head_dim, eps):
    """ONE group of a Mamba-2 mixer: h (S, D) normed; w_z, w_x (D, n hd)
    the group's heads' columns; w_b, w_c (D, N) the group's B and C; w_dt
    (D, n); conv_* (taps, .) and bias_* the conv over each; dt_bias, a_log,
    d_skip (n,); norm_w (n hd,); keep () int32.  -> (the gated, group-normed
    output (S, n hd), the group's states S_keep (n, hd, N): what the
    recurrence holds once position ``keep`` is in it)."""
    s = h.shape[0]

    def conv_act(x, w, b):
        """silu of the causal depthwise conv: tap j reads x_{t-(K-1)+j}."""
        taps = w.shape[0]
        acc = jnp.zeros_like(x) + b
        for j in range(taps):
            back = taps - 1 - j
            acc = acc + w[j] * jnp.pad(x, ((back, 0), (0, 0)))[:s]
        return jax.nn.silu(acc)

    z = h @ w_z.astype(F32)
    x = conv_act(h @ w_x.astype(F32), conv_x, bias_x).reshape(s, -1, head_dim)
    b = conv_act(h @ w_b.astype(F32), conv_b, bias_b)            # (S, N)
    c = conv_act(h @ w_c.astype(F32), conv_c, bias_c)
    dt = jax.nn.softplus(h @ w_dt.astype(F32) + dt_bias)         # (S, n)
    a = -jnp.exp(a_log)

    def position(carry, inputs):
        state, kept = carry
        x_t, b_t, c_t, dt_t, t = inputs        # (n, hd) (N,) (N,) (n,) ()
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return ((state, jnp.where(t == keep, state, kept)),
                jnp.einsum("hdn,n->hd", state, c_t))

    zero = jnp.zeros((x.shape[1], head_dim, b.shape[1]), F32)
    (_, kept), y = jax.lax.scan(position, (zero, zero),
                                (x, b, c, dt, jnp.arange(s)))
    y = (y + d_skip[:, None] * x).reshape(s, -1)
    return _rms_norm(y * jax.nn.silu(z), norm_w, eps), kept


def _attention_group(h, wq, wk, wv, head_dim):
    """One kv head's queries: h (S, D) normed, wq (D, g d), wk, wv (D, d)
    -> the attention output (S, g d)."""
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
    return out.reshape(s, -1)


_mamba_jit = jax.jit(_mamba_group, static_argnums=(17, 18))
_attention_jit = jax.jit(_attention_group, static_argnums=(4,))


def _norm_in(x, scale, eps):
    return _rms_norm(x, scale.astype(F32), eps)


def _add_projected(x, y, w):
    return x + y @ w.astype(F32)


_norm_jit = jax.jit(_norm_in, static_argnums=(2,))
_add_projected_jit = jax.jit(_add_projected)


def _mamba(x, w, c, keep):
    """x (S, D) -> (x + the Mamba-2 mixer, a group of heads a call; the
    block's states (nh, hd, N) once position ``keep`` is in them)."""
    eps = float(c["norm_eps"])
    nh, hd = c["mamba_num_heads"], c["mamba_head_dim"]
    n, groups = c["ssm_state_size"], c["n_groups"]
    d_inner, per = nh * hd, nh // groups
    h = _norm_jit(x, w["attn_norm"], eps)
    f32 = {k: w[k].astype(F32) for k in (
        "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias", "ssm_A_log", "ssm_D",
        "ssm_norm")}
    outs, states = [], []
    for g in range(groups):
        heads = slice(g * per, (g + 1) * per)
        chan = slice(g * per * hd, (g + 1) * per * hd)
        # columns of the conv's input xBC: x | B | C
        at_b = slice(d_inner + g * n, d_inner + (g + 1) * n)
        at_c = slice(d_inner + (groups + g) * n,
                     d_inner + (groups + g + 1) * n)

        def shifted(cols):      # ssm_in's columns are [z | xBC]
            return slice(d_inner + cols.start, d_inner + cols.stop)

        out, kept = _mamba_jit(
            h, w["ssm_in"][:, chan],
            *(w["ssm_in"][:, shifted(cols)] for cols in (chan, at_b, at_c)),
            w["ssm_dt"][:, heads],
            *(f32["ssm_conv_w"][:, cols] for cols in (chan, at_b, at_c)),
            *(f32["ssm_conv_b"][cols] for cols in (chan, at_b, at_c)),
            f32["ssm_dt_bias"][heads], f32["ssm_A_log"][heads],
            f32["ssm_D"][heads], f32["ssm_norm"][chan], keep, hd, eps)
        outs.append(out)
        states.append(kept)
    return (_add_projected_jit(x, jnp.concatenate(outs, -1), w["ssm_out"]),
            jnp.concatenate(states))


def _attention(x, w, c):
    """x (S, D) -> x + attention, a kv head's queries a call."""
    eps, d = float(c["norm_eps"]), c["head_dim"]
    heads, kv = c["num_attention_heads"], c["num_key_value_heads"]
    h = _norm_jit(x, w["attn_norm"], eps)
    g = heads // kv * d
    out = jnp.concatenate([
        _attention_jit(h, w["wq"][:, i * g:(i + 1) * g],
                       w["wk"][:, i * d:(i + 1) * d],
                       w["wv"][:, i * d:(i + 1) * d], d)
        for i in range(kv)], -1)
    return _add_projected_jit(x, out, w["wo"])


# ------------------------------------------------------------------- FFN
def _experts(x, w, eps, first, top_k, norm_topk, scale):
    """x (S, D) -> (x + the shared expert + W_2 of the HELD chosen experts'
    gated sum in the latent, the experts chosen (S, k))."""
    h = _rms_norm(x, w["mlp_norm"].astype(F32), eps)
    scores = jax.nn.sigmoid(h @ w["router"].astype(F32))
    _, chosen = jax.lax.top_k(scores + w["router_bias"].astype(F32), top_k)
    member = jax.nn.one_hot(chosen, scores.shape[-1], dtype=F32).sum(-2)
    gates = scores * member
    if norm_topk:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-6)
    gates = gates * scale
    held = w["w_up"].shape[0]
    u = h @ w["w_lat_in"].astype(F32)

    def relu2(rows, up, down):
        return jnp.square(jax.nn.relu(rows @ up.astype(F32))) \
            @ down.astype(F32)

    def one(y, ws):
        up, down, g = ws
        return y + g[:, None] * relu2(u, up, down), None

    latent = jax.lax.scan(one, jnp.zeros_like(u),
                          (w["w_up"], w["w_down"],
                           gates[:, first:first + held].T))[0]
    return (x + latent @ w["w_lat_out"].astype(F32)
            + relu2(h, w["ws_up"], w["ws_down"])), chosen


_experts_jit = jax.jit(_experts, static_argnums=(2, 3, 4, 5, 6))


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
def _pattern(config: Dict[str, Any]) -> str:
    """The blocks' characters, after what is not modelled is refused."""
    for key, want in (("mlp_hidden_act", "relu2"), ("n_group", 1),
                      ("topk_group", 1), ("tie_word_embeddings", False),
                      ("use_bias", False), ("mamba_proj_bias", False),
                      ("attention_bias", False), ("use_conv_bias", True),
                      ("n_shared_experts", 1)):
        if config.get(key, want) != want:
            raise ValueError(f"nemotron_h_decoder: {key}={config[key]!r} "
                             f"is not modelled")
    pattern = config["hybrid_override_pattern"]
    if len(pattern) != config["num_hidden_layers"] or set(pattern) - set(
            "M*E"):
        raise ValueError("nemotron_h_decoder: hybrid_override_pattern names "
                         "each of the num_hidden_layers blocks M, * or E")
    return pattern


def _locate(params, name: str, index: int):
    """The ``index``-th row of leaf ``name`` over the parts ``layers``,
    ``layers_1``, ... in their order."""
    keys = sorted((k for k in params if k == "layers"
                   or k.startswith("layers_") and k[7:].isdigit()),
                  key=lambda k: int(k[7:] or 0))
    for key in keys:
        leaf = params[key].get(name)
        if leaf is None:
            continue
        if index < leaf.shape[0]:
            return leaf[index]
        index -= leaf.shape[0]
    raise ValueError(f"nemotron_h_decoder: no row {index} of {name}")


def _block_weights(params, pattern: str, i: int):
    """Block ``i``'s leaves, each counted among the blocks that have it."""
    kind = pattern[i]
    before = pattern[:i]
    if kind == "E":
        return {name: _locate(params, name, before.count("E"))
                for name in EXPERT_LEAVES}
    mine = MAMBA_LEAVES if kind == "M" else ATTENTION_LEAVES
    out = {name: _locate(params, name, before.count(kind)) for name in mine}
    out["attn_norm"] = _locate(params, "attn_norm",
                               len(before) - before.count("E"))
    return out


def _padded(tokens):
    """The row lengthened with zeros to whole blocks (what follows a
    position never reaches it)."""
    s = len(tokens)
    if s <= QUERY_BLOCK:
        return tokens
    return np.concatenate([tokens, np.zeros(-s % POSITION_BLOCK, np.int32)])


def _hidden(params, tokens, config, keep: int = 0):
    """For ONE row of tokens (S,): the last block's output (S, D), the
    experts each E block chose (Le, S, k) and every M block's states (Lm,
    nh, hd, N) once position ``keep`` is in them."""
    pattern = _pattern(config)
    share = config.get("share", {})
    routing = (float(config["norm_eps"]), share.get("experts_first", 0),
               config["num_experts_per_tok"], bool(config["norm_topk_prob"]),
               float(config["routed_scaling_factor"]))
    x = _embed_jit(params["embed_tokens"], jnp.asarray(tokens))
    chosen, states = [], []
    keep = jnp.asarray(keep, jnp.int32)
    for i, kind in enumerate(pattern):
        w = _block_weights(params, pattern, i)
        if kind == "E":
            x, picked = _experts_jit(x, w, *routing)
            chosen.append(picked)
        elif kind == "M":
            x, kept = _mamba(x, w, config, keep)
            states.append(kept)
        else:
            x = _attention(x, w, config)
    return x, jnp.stack(chosen), jnp.stack(states)


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any]):
    """(B, S, V) float32 logits for ``tokens`` (B, S) int32.  ``config``
    is the configuration file's dict (published key names).  The whole
    vocabulary at every position: for short rows."""
    tokens = np.asarray(tokens, np.int32)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _head_jit(_hidden(params, _padded(row), config)[0][:len(row)],
                      params["final_norm"], params["lm_head"],
                      float(config["norm_eps"]))
            for row in tokens])


def teacher_forced_report(params: Dict[str, Any], prompt, emitted,
                          config: Dict[str, Any], pad_to: int = 0):
    """For a greedy decoder's ``emitted`` tokens after ``prompt``, one full
    forward pass over prompt + emitted.  Per emitted token, at the position
    that produced it: ``gap``, the reference's top logit minus the
    reference's logit of the token that was emitted (0 where they agree);
    ``chosen`` (Le, n, k), the experts each E block of the reference
    chose there; and ``states`` (Lm, nh, hd, N), what every M block's
    recurrence holds when the LAST emitted token is produced (prompt and
    every emitted token but the last in it: what a cache holds of the
    request).  ``pad_to`` lengthens the row with zeros to one compiled
    shape: the conv, the recurrence and the attention are causal, and an
    expert block mixes no positions."""
    seq = list(prompt) + list(emitted)
    seq = _padded(np.asarray(seq + [0] * max(0, pad_to - len(seq)),
                             np.int32))
    at = slice(len(prompt) - 1, len(prompt) - 1 + len(emitted))
    with jax.default_matmul_precision("highest"):
        x, chosen, states = _hidden(params, seq, config,
                                    keep=len(prompt) + len(emitted) - 2)
        gap = np.asarray(_head_gap_jit(
            x, params["final_norm"], params["lm_head"],
            jnp.asarray(np.roll(seq, -1)), float(config["norm_eps"])))
    return {"gap": gap[at], "chosen": np.asarray(chosen)[:, at],
            "states": np.asarray(states)}


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


def served_deviation(params: Dict[str, Any], prompt, emitted,
                     config: Dict[str, Any], max_len: int, states,
                     cfg=None) -> Dict[str, list]:
    """Per M block, how far the states that the PROGRAM holds of the request
    lie from ``states``, the recurrence's own (``teacher_forced_report``),
    over the whole state and over its furthest head:
    ``lib/nemotron_state.py`` takes the request through the engine's programs
    once more, in a cache of the engine's geometry.  ``cfg``: the program's
    configuration where it is not the file's (a tool's broken variant)."""
    from benchmarks.lib import nemotron_state, program

    if cfg is None:
        cfg = program.llama_config(config, max_seq_len=max_len)
    served = nemotron_state.served_states(
        cfg, params, prompt, emitted, max_len=max_len,
        **nemotron_state.geometry(config))
    return nemotron_state.deviation(served, states)


def judged(raw: np.ndarray, deviations) -> np.ndarray:
    """What ``kinds/serve_llm.py`` takes the largest of and holds under its
    LOGIT_MARGIN (0.25): a request's gaps with its near-tie swaps taken out
    (``take_out_swaps``) and, LAST, the deviation of the first M block's
    furthest head in units of STATE_LIMIT where it is over it (>= 1: not
    correct), else 0."""
    over = deviations["head"][0] / STATE_LIMIT
    return np.append(take_out_swaps(raw), over if over > 1.0 else 0.0)


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """``judged`` of one request: ``teacher_forced_report``'s gap at each
    emitted position and the distance of the program's recurrent states from
    the recurrence's (``served_deviation``), and one ``reference_gaps`` line
    of what was read (for the record a run leaves)."""
    report = teacher_forced_report(params, prompt, emitted, config, pad_to)
    deviations = served_deviation(
        params, prompt, emitted, config,
        pad_to or len(prompt) + len(emitted), report["states"])
    out = judged(report["gap"], deviations)
    # (the second cache beside the idle engine's: how near the device's
    # memory the check came, which the run's own peak is read too early for)
    memory = jax.devices()[0].memory_stats() or {}
    print(json.dumps({"event": "reference_gaps",
                      **gap_counts(report["gap"]),
                      "state_deviation": deviations,
                      "hbm_peak_bytes": memory.get("peak_bytes_in_use"),
                      "judged_max": float(out.max())}), flush=True)
    return out


# Over SWAP_GAP a position's gap is a swap of experts at a near-tie, not
# rounding; over SWAP_CEILING it is no swap either.
SWAP_GAP = 0.05
SWAP_CEILING = 2.0
SWAP_SHARE = 5          # a request's positions over SWAP_GAP: 1 in this many
# The first M block's states against the recurrence's, |difference| / |state|
# of the head that is furthest (``judged``; the readings in ``take_out_swaps``'
# docstring).
STATE_LIMIT = 1.2e-2


def swaps_allowed(n: int) -> int:
    """Of a request's ``n`` emitted positions, how many may read over
    SWAP_GAP: a fifth and four more, which keeps a short request's count
    from deciding by chance."""
    return 4 + n // SWAP_SHARE


def take_out_swaps(gap: np.ndarray) -> np.ndarray:
    """A request's gaps with those over SWAP_GAP set to zero, if they are at
    most ``swaps_allowed`` and none is over SWAP_CEILING; as they were read
    otherwise (``solar_open2_decoder.take_out_swaps``'s form).

    Why a count.  The 22 of 512 experts a token takes are those with the
    largest sigmoid score + bias; under random weights the 22nd and 23rd lie
    closer than a bfloat16 stream's rounding moves a score in some expert
    block for a share of tokens, the engine then takes the other expert and
    -- where that expert or the one it displaced is among the 128 held here,
    one pick in four -- adds or leaves out one expert's output, which the
    recurrent states and the K/V rows carry to the positions after.
    Measured on the chip at the published widths (PERF.md section 6, PR 61:
    the cell's checked requests, 96 slots, and tools/nemotron_check.py): a
    sound engine's request reads 4.0-11.2% of its emitted positions over
    0.05 (29 of 260 the most, 64 of 1,024 and 85 of 1,024 typical of the
    long ones), the rest under 0.05 and mostly exactly 0 (the same token
    leads), its largest gap 1.02; tests/test_nemotron_h_serve.py holds every
    position in float32, where no tie breaks differently (3.7e-6 of a
    deviation in LOGITS).

    The limits, each between two readings.  A fifth of a request's positions
    (and four): between 11.2%, the most a sound request read, and 43%, what
    the mildest broken program that the count can see reads (weights in
    float8_e4m3's mantissa, the precision below the configuration's
    bfloat16: 221 of 512; the routed scale dropped 61%, the gated norm over
    the whole width 64%, one group's B and C for every head 74%, relu for
    relu^2 86%).  One position over 2.0: between the largest gap any
    sound position read (1.02, one of ~40,000 positions; the next 0.78), and
    3.66, the median of what an arbitrary token reads among the 32,768
    logits (deviation 0.88; 1.53-5.87 from the 1st to the 99th percentile,
    96.7% of them over 2.0: a stale state or cache row gives such tokens at
    a few positions only, which a count would let through).

    What the tokens do NOT show, and ``judged`` reads from the STATE instead
    (STATE_LIMIT; ``lib/nemotron_state.py``): the recurrent state kept in
    bfloat16, or the recurrence run in it, where the file says float32 reads
    as a sound engine does by its tokens (42-55 of 512 and 77-84 of 1,024
    positions over 0.05, and 39-43 and 83-89, against 36-47 and 72-76
    intact).  The first M block's states, read back
    from the slot at the cell's 96 slots after the request was taken through
    the programs once more, against this file's recurrence, by the head that
    is furthest (|difference| / |state|; that block reads the embedding rows,
    which no expert swap reaches: the later blocks' states lie 0.03-0.33 off
    in a sound engine and tell nothing): a sound engine 0.0046-0.0059 (ten
    readings, three seeds, 512 and 1,024 decoded; 0.0044-0.0064 over the 52
    checked requests of the cell's thirteen runs: a bfloat16 stream's
    rounding of what enters the state, the same share of every head), the
    recurrence run in bfloat16 0.0235-0.0719 (five readings), the state kept
    in bfloat16 0.0392-0.0520 (five): a rounding a step, which the slowest
    head gathers.  The limit 0.012: 1.9 times the largest sound reading, half
    the smallest broken one.  (Over the block's WHOLE state the three read
    0.0035-0.0041, 0.0049-0.0066 and 0.0053-0.0068: no limit fits between,
    which is why it is the furthest head.)  My chip runs, PR 61, PERF.md
    section 6."""
    swapped = gap > SWAP_GAP
    if (swapped.sum() > swaps_allowed(len(gap))
            or (gap > SWAP_CEILING).any()):
        return gap
    return np.where(swapped, 0.0, gap)

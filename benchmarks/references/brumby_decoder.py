"""Plain reference of Brumby-14B-Base's decoder (``model_type: brumby``: the
published ``config.json`` as the catalog beside the model-configs guide
holds it, Qwen3-14B's shape; the layer's equations as Manifest AI's *Scaling
Context Requires Rethinking Attention*, arXiv:2507.04239, and the public
``retention`` package's ``power_retention`` write them at degree 2, each
reading listed in the configuration file's ``assumed``).  Every layer is
pre-norm, no biases, and NO layer attends by softmax:

    x <- x + Retention(RMSNorm(x; g_attn));  x <- x + SwiGLU(RMSNorm(x; g_mlp))

Retention, ``n`` a query head of 40, ``m = n // 5`` its key/value head of 8,
``d = 128``, written here in its ATTENTION form:

    q_n = RoPE(rmsnorm_head(h W_q)_n);  k_m = RoPE(rmsnorm_head(h W_k)_m)
    v_m = (h W_v)_m;   gamma_m = log sigmoid((h W_g)_m + b_m)     float32
    a_n(t, i) = exp(G_m(t) - G_m(i)) (q_n(t) . k_m(i))^2          i <= t
    o_n(t)    = sum_i a_n(t, i) v_m(i) / (sum_i a_n(t, i) + eps)
    out       = concat_n(o_n) W_o

``G_m`` the running sum of ``gamma_m``; ``b_m`` the configuration's
``gate_shift`` (two ends, evenly spaced over the heads: ``assumed``; 0
where the file names none); RoPE
in the rotate-half convention at ``rope_theta``; then a final RMSNorm and
the untied output head.

Straight ``jax.numpy`` in float32 under
``default_matmul_precision("highest")``: no kernel, no cache, no state
handed on.  The weights ``a`` are computed as they are written, a block of
queries against every key: this file never builds ``phi`` for its logits,
so it shares neither code nor algebra with the engine's state form (the
engine keeps ``S = sum_i e^(G(t) - G(i)) phi(k_i) v_i^T`` and reads it with
``phi(q)``).  For the check of the STATE itself (``state_sums``) the sum
above is made directly for every layer's heads, from the forward pass's own
layer inputs, and handed back over the exact triangle of ``d (d + 1) / 2 =
8,256`` products (``triangle``), whatever layout the engine keeps.  It
shares nothing with ``ray_tpu/models/`` but the parameter tree's key names,
each leaf stacked over the layers (and cut out of the stack where it is
used: a layer's 0.66 GB is never copied whole):

    embed_tokens (V, D); lm_head (D, V); final_norm (D,); layers:
    attn_norm, mlp_norm (L, D); w_gate, w_up (L, D, F); w_down (L, F, D);
    power_q (L, D, Hq d); power_k, power_v (L, D, Hkv d); power_g (L, D,
    Hkv); power_q_norm, power_k_norm (L, d); power_o (L, Hq d, D)

The benchmark pads every checked row to the engine's ``max_len`` (18,432)
and runs this beside the loaded engine, so a layer is a few jitted calls
over a key/value head's group or a block of positions each, and the head
runs a block of positions against a slice of the vocabulary at a time,
keeping only the top logit and the next token's.
"""

from __future__ import annotations

import functools
import json
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256        # 5 heads x 256 x 18,432 float32 weights: 94 MB
POSITION_BLOCK = 1024
FFN_SLICES = 8           # of the feed-forward width, a call
VOCAB_SLICES = 4


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _blocks(x, size):
    """(S, ...) -> (S / size, size, ...)."""
    return x.reshape((x.shape[0] // size, size) + x.shape[1:])


def _rope(x, theta):
    """x (S, H, d), position = row: the rotate-half convention."""
    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=F32) / (d // 2))
    angles = jnp.arange(s, dtype=F32)[:, None] * freqs
    sin, cos = jnp.sin(angles)[:, None], jnp.cos(angles)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gate_shift(config: Dict[str, Any]) -> np.ndarray:
    """``b_m`` (Hkv,): the configuration's ``gate_shift`` ends and evenly
    between (zeros without one)."""
    lo, hi = config.get("gate_shift") or (0.0, 0.0)
    return np.linspace(lo, hi, config["num_key_value_heads"]).astype(
        np.float32)


# --------------------------------------------------------------- the layer
def _heads_of_group(h, wq, wk, wv, wg, q_norm, k_norm, shift, head_dim,
                    theta, eps):
    """One key/value head and its group: h (S, D) normed -> (q (S, R, d), k
    (S, d), v (S, d), G (S,) the running log-decay)."""
    s = h.shape[0]
    q = (h @ wq.astype(F32)).reshape(s, -1, head_dim)
    k = (h @ wk.astype(F32)).reshape(s, 1, head_dim)
    v = h @ wv.astype(F32)
    q = _rope(_rms_norm(q, q_norm.astype(F32), eps), theta)
    k = _rope(_rms_norm(k, k_norm.astype(F32), eps), theta)[:, 0]
    gamma = jax.nn.log_sigmoid((h @ wg.astype(F32))[:, 0] + shift)
    return q, k, v, jnp.cumsum(gamma)


def _retention_group(x, wo, h, wq, wk, wv, wg, q_norm, k_norm, shift,
                     head_dim, theta, eps, power_eps):
    """-> x + the group's outputs (S, R d), the attention form, through
    their rows ``wo`` of W_o (``concat_n(o_n) W_o`` a group at a time: no
    layer's outputs lie side by side)."""
    q, k, v, G = _heads_of_group(h, wq, wk, wv, wg, q_norm, k_norm, shift,
                                 head_dim, theta, eps)
    s = h.shape[0]
    size = min(QUERY_BLOCK, s)
    j = jnp.arange(s)[None, :]

    def block(args):
        qb, Gb, i = args
        scores = jnp.einsum("qrd,kd->rqk", qb, k)
        decay = jnp.exp(jnp.where(j <= i[:, None],
                                  Gb[:, None] - G[None, :], -jnp.inf))
        a = decay[None] * scores * scores
        num = jnp.einsum("rqk,kd->qrd", a, v)
        den = jnp.moveaxis(jnp.sum(a, -1), 0, 1)
        return num / (den + power_eps)[..., None]

    out = jax.lax.map(block, (_blocks(q, size), _blocks(G, size),
                              _blocks(jnp.arange(s), size)))
    return x + out.reshape(s, -1) @ wo.astype(F32)




def _norm_in(x, scale, eps):
    return _rms_norm(x, scale.astype(F32), eps)


def _swiglu_slice(x, h, gate, up, down):
    """x (S, D) + a slice of the feed-forward width's part of the result
    for h (S, D), a block of positions at a time."""
    size = min(POSITION_BLOCK, h.shape[0])

    def block(args):
        xb, hb = args
        return xb + (jax.nn.silu(hb @ gate.astype(F32))
                     * (hb @ up.astype(F32))) @ down.astype(F32)

    return jax.lax.map(block, (_blocks(x, size), _blocks(h, size))
                       ).reshape(x.shape)


_norm_jit = jax.jit(_norm_in, static_argnums=(2,))


@functools.cache
def _stream_jits():
    """``(_retention_group, _swiglu_slice)`` jitted: the stream handed in
    is the stream handed back, on the chip, where the check runs beside a
    loaded engine, in the same bytes (a CPU donates nothing; asked at the
    first call, not at import: no backend is touched before the harness
    has set it up)."""
    donate = (0,) if jax.default_backend() == "tpu" else ()
    return (jax.jit(_retention_group, static_argnums=(10, 11, 12, 13),
                    donate_argnums=donate),
            jax.jit(_swiglu_slice, donate_argnums=donate))


def _cut(layers, i: int, name: str, *at):
    """Layer ``i``'s part ``at`` of leaf ``name``, out of the stack."""
    return layers[name][(i,) + at]


def _group_weights(w, c, m: int):
    """``w``: ``_cut`` of one layer."""
    d, rows = c["head_dim"], slice(None)
    g = c["num_attention_heads"] // c["num_key_value_heads"] * d
    return (w("power_q", rows, slice(m * g, (m + 1) * g)),
            w("power_k", rows, slice(m * d, (m + 1) * d)),
            w("power_v", rows, slice(m * d, (m + 1) * d)),
            w("power_g", rows, slice(m, m + 1)),
            w("power_q_norm"), w("power_k_norm"),
            float(gate_shift(c)[m]), d, float(c["rope_theta"]),
            float(c["rms_norm_eps"]))


def _layer(x, w, c, states_at=None):
    """One block for x (S, D): ``(x after it, what its key/value heads hold
    after ``states_at`` positions or None)``.  Each call hands on ``x +
    its part``, a group or a slice of the feed-forward width at a time, so
    that beside the stream and its normed form no third row-sized array
    has to live (``x`` is a list of one: the caller keeps no second
    reference to what a call has replaced, and on the chip a call takes
    the stream's own bytes for its result)."""
    eps = float(c["rms_norm_eps"])
    h = _norm_jit(x[0], w("attn_norm"), eps)
    states = None if states_at is None \
        else _states_of_layer(h, w, c, states_at)
    retention, swiglu = _stream_jits()
    d = c["head_dim"]
    g = c["num_attention_heads"] // c["num_key_value_heads"] * d
    for m in range(c["num_key_value_heads"]):
        x[0] = retention(
            x[0], w("power_o", slice(m * g, (m + 1) * g)), h,
            *_group_weights(w, c, m), float(c["power_eps"]))
    h = _norm_jit(x[0], w("mlp_norm"), eps)
    width = c["intermediate_size"]
    slices = FFN_SLICES if width % FFN_SLICES == 0 else 1
    for i in range(slices):
        at = slice(i * width // slices, (i + 1) * width // slices)
        x[0] = swiglu(x[0], h, w("w_gate", slice(None), at),
                      w("w_up", slice(None), at), w("w_down", at))
    # (no layer is enqueued before the one before it has run: what the
    # device holds at once is what one layer holds)
    x[0].block_until_ready()
    return states


def _embed(table, tokens):
    return table[tokens].astype(F32)


def _head_gap(x, final_norm, head, nxt, eps):
    """Per position: the top logit minus the logit of ``nxt``.  A block of
    positions against a slice of the vocabulary's columns at a time (cut
    out of the head where it lies: no copy of its 1.56 GB is made)."""
    s, vocab = x.shape[0], head.shape[1]
    size = min(POSITION_BLOCK, s)
    slices = VOCAB_SLICES if vocab % VOCAB_SLICES == 0 else 1
    width = vocab // slices
    x = _rms_norm(x, final_norm.astype(F32), eps)

    def block(args):
        xb, nb = args

        def part(carry, first):
            top, own = carry
            cols = jax.lax.dynamic_slice_in_dim(head, first, width, axis=1)
            lg = xb @ cols.astype(F32)                      # (size, width)
            at = jnp.clip(nb - first, 0, width - 1)
            mine = jnp.take_along_axis(lg, at[:, None], -1)[:, 0]
            inside = (nb >= first) & (nb < first + width)
            return (jnp.maximum(top, lg.max(-1)),
                    jnp.where(inside, mine, own)), None

        (top, own), _ = jax.lax.scan(
            part, (jnp.full((size,), -jnp.inf, F32), jnp.zeros((size,), F32)),
            jnp.arange(slices) * width)
        return top - own

    return jax.lax.map(block, (_blocks(x, size), _blocks(nxt, size))
                       ).reshape(s)


def _head(x, final_norm, head, eps):
    return _rms_norm(x, final_norm.astype(F32), eps) @ head.astype(F32)


_embed_jit = jax.jit(_embed)
_head_jit = jax.jit(_head, static_argnums=(3,))
_head_gap_jit = jax.jit(_head_gap, static_argnums=(4,))


# ----------------------------------------------------------------- model
def _checked(config: Dict[str, Any]):
    for key, want in (("model_type", "brumby"), ("attention_bias", False),
                      ("hidden_act", "silu"), ("rope_scaling", None),
                      ("tie_word_embeddings", False),
                      ("use_sliding_window", False), ("power_degree", 2)):
        if config.get(key, want) != want:
            raise ValueError(f"brumby_decoder: {key}={config[key]!r} is "
                             f"not modelled")


def _padded(tokens):
    """The row lengthened with zeros to whole blocks (what follows a
    position never reaches it)."""
    s = len(tokens)
    if s <= QUERY_BLOCK:
        return tokens
    return np.concatenate([tokens, np.zeros(-s % POSITION_BLOCK, np.int32)])


def _hidden(params, tokens, config, states_at=None):
    """For ONE row of tokens (S,): the last layer's output (S, D) and, where
    ``states_at`` is a number of positions, what every layer's key/value
    heads hold after that many (``_states_of_layer``, stacked over the
    layers; None else)."""
    _checked(config)
    x = [_embed_jit(params["embed_tokens"], jnp.asarray(tokens))]
    states = [_layer(x, functools.partial(_cut, params["layers"], i),
                     config, states_at)
              for i in range(config["num_hidden_layers"])]
    if states_at is None:
        return x[0], None
    return x[0], tuple(np.stack(part) for part in zip(*states))


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


# ------------------------------------------------------ the state, directly
def triangle(d: int):
    """``(a, b, weight)`` of the ``d (d + 1) / 2`` entries of the symmetric
    square of a vector of ``d``: ``a <= b``, the off-diagonal ones times
    sqrt 2."""
    a, b = np.triu_indices(d)
    return a, b, np.where(a == b, 1.0, np.sqrt(2.0)).astype(np.float32)


def _state_of_head(k, v, G, n):
    """``S(n) = sum_{i < n} exp(G(n - 1) - G(i)) phi(k_i) v_i^T`` (D, d) and
    ``z(n)`` (D,) of one head: k, v (S, d), G (S,), the first ``n``
    positions, a block at a time.  Summed over the whole square ``k k^T``,
    of which ``phi`` is the upper triangle (an off-diagonal product lies in
    the square twice, in ``phi`` once times sqrt 2): no gather a block."""
    s, d = k.shape
    a, b, w = triangle(d)
    size = min(POSITION_BLOCK, s)
    last = G[jnp.maximum(n - 1, 0)]

    def block(carry, xs):
        S, z = carry
        kb, vb, Gb, i = xs
        weight = jnp.where(i < n, jnp.exp(jnp.minimum(last - Gb, 0.0)), 0.0)
        square = (kb * weight[:, None])[:, :, None] * kb[:, None, :]
        return (S + jnp.einsum("iab,ie->abe", square, vb),
                z + square.sum(0)), None

    (S, z), _ = jax.lax.scan(
        block, (jnp.zeros((d, d, d), F32), jnp.zeros((d, d), F32)),
        (_blocks(k, size), _blocks(v, size), _blocks(G, size),
         _blocks(jnp.arange(s), size)))
    return S[a, b] * w[:, None], z[a, b] * w


def _state_of_group(h, wq, wk, wv, wg, q_norm, k_norm, shift, n, head_dim,
                    theta, eps):
    _q, k, v, G = _heads_of_group(h, wq, wk, wv, wg, q_norm, k_norm, shift,
                                  head_dim, theta, eps)
    return _state_of_head(k, v, G, n)


_state_jit = jax.jit(_state_of_group, static_argnums=(9, 10, 11))


def _states_of_layer(h, w, c, n: int):
    """What a layer's key/value heads hold after the first ``n`` positions
    of its normed input h (S, D): ``(S (Hkv, D, d), z (Hkv, D))`` float32
    (numpy), ``D = d (d + 1) / 2`` in ``triangle``'s order."""
    S, z = [], []
    for m in range(c["num_key_value_heads"]):
        *weights, shift, d, theta, eps = _group_weights(w, c, m)
        Sm, zm = _state_jit(h, *weights, shift, jnp.int32(n), d, theta, eps)
        S.append(np.asarray(Sm))
        z.append(np.asarray(zm))
    return np.stack(S), np.stack(z)


def state_sums(params: Dict[str, Any], tokens, config: Dict[str, Any],
               n: int):
    """What every layer's key/value heads hold after the first ``n`` of
    ``tokens``: ``(S (L, Hkv, D, d), z (L, Hkv, D))`` float32 (numpy), the
    sum as it is written over the forward pass's own layer inputs."""
    with jax.default_matmul_precision("highest"):
        return _hidden(params, _padded(np.asarray(tokens, np.int32)),
                       config, states_at=n)[1]


# --------------------------------------------------------- what is compared
def teacher_forced_report(params: Dict[str, Any], prompt, emitted,
                          config: Dict[str, Any], pad_to: int = 0,
                          states_at=None):
    """For a greedy decoder's ``emitted`` tokens after ``prompt``, one full
    forward pass over prompt + emitted.  Per emitted token, at the position
    that produced it: ``gap``, the reference's top logit minus the
    reference's logit of the token that was emitted (0 where they agree).
    ``pad_to`` lengthens the row with zeros to one compiled shape: every
    sum here is causal.  ``states``: ``state_sums`` of the row's first
    ``states_at`` positions from the same pass (None without)."""
    seq = list(prompt) + list(emitted)
    seq = _padded(np.asarray(seq + [0] * max(0, pad_to - len(seq)),
                             np.int32))
    first, n = len(prompt) - 1, len(emitted)
    # the head at the emitted positions alone (a whole number of blocks of
    # them: one compiled shape for replies of up to POSITION_BLOCK tokens)
    rows = -(-n // POSITION_BLOCK) * POSITION_BLOCK if n > QUERY_BLOCK else n
    first = max(0, min(first, len(seq) - rows))
    at = slice(len(prompt) - 1 - first, len(prompt) - 1 - first + n)
    with jax.default_matmul_precision("highest"):
        x, states = _hidden(params, seq, config, states_at)
        gap = np.asarray(_head_gap_jit(
            x[first:first + rows], params["final_norm"], params["lm_head"],
            jnp.asarray(np.roll(seq, -1)[first:first + rows]),
            float(config["rms_norm_eps"])))
    return {"gap": gap[at], "states": states}


def gap_counts(gap: np.ndarray) -> Dict[str, Any]:
    """What a request's gaps look like, for the record a run prints."""
    top = np.sort(gap)[::-1][:6]
    return {"positions": int(len(gap)), "max": float(gap.max()),
            "mean": float(gap.mean()),
            "over_0.03": int((gap > 0.03).sum()),
            "over_0.1": int((gap > 0.1).sum()),
            "over_0.25": int((gap > 0.25).sum()),
            "top": [round(float(g), 4) for g in top]}


def judged(raw: np.ndarray, deviations) -> np.ndarray:
    """What ``correct`` compares with the harness's logit margin: a
    request's gaps as they were read (a dense model has no near-tie of
    experts to take out), with an infinite one put in front where the
    states lie too far from the sum as it is written: the FIRST layer's
    furthest head past STATE_LIMIT (its input is the embedding's rows, the
    same on both sides, so every head's distance is the state's own
    arithmetic), or a LATER layer's whole state past LATER_STATE_LIMIT (its
    input carries the bfloat16 stream's rounding of the layers before,
    which a fast head, a sum of a few hundred positions, averages least:
    the whole state, mostly the slow heads', is what a rounding a step
    moves and the stream's rounding hardly does)."""
    first, later = deviations["head"][0], deviations["whole"][1:]
    if max(first) > STATE_LIMIT \
            or max(later, default=0.0) > LATER_STATE_LIMIT:
        return np.concatenate([[np.inf], raw])
    return raw


def _peak_bytes():
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """``judged`` of one request: ``teacher_forced_report``'s gap at each
    emitted position, and the distance of what the LIVE engine's own
    programs leave in a slot of its own cache, every layer, from the sum as
    it is written (``lib/power_state.served_states``: the request taken
    once more through them, whole decode chunks that feed on their own
    tokens).  Where those tokens are the reply's, the states are summed in
    the one forward pass that reads the gaps; where a near-tie parted them,
    in a second pass over the tokens the slot did take in.  One
    ``reference_gaps`` line of what was read, and the device's peak bytes
    after each part (for the record a run leaves)."""
    from benchmarks.lib import power_state

    served = power_state.served_states(params, prompt, emitted)
    peaks = {"replay": _peak_bytes()}
    taken = served["tokens"][:-1]
    same = list(emitted[:len(taken)]) == taken
    report = teacher_forced_report(
        params, prompt, emitted, config, pad_to,
        states_at=served["positions"] if same else None)
    peaks["forward"] = _peak_bytes()
    states = report["states"] if same else state_sums(
        params, list(prompt) + taken, config, served["positions"])
    deviations = power_state.deviation(served["S"], served["z"], *states)
    raw = report["gap"]
    out = judged(raw, deviations)
    print(json.dumps({
        "event": "reference_gaps", **gap_counts(raw),
        "state_deviation": deviations,
        "state_of": {"slot": served["slot"], "slots": served["slots"],
                     "k": served["k"], "positions": served["positions"],
                     "tokens_are_the_replys": same},
        "hbm_peak_bytes": peaks,
        "judged_max": float(out.max())}), flush=True)
    return out


# The states the live engine's own programs leave in a slot of its own cache
# (16 slots advancing, its own chunk of 16, every layer read) against the sum
# as it is written, |difference| / |state| (``judged``).  Each limit between
# two readings on the chip at the published widths (my chip runs, PR 65;
# PERF.md section 6 (j)): sound, the checked requests of the cell's runs and
# ``tools/power_check.py``'s intact engine; broken, the state KEPT in
# bfloat16 / the update RUN in bfloat16, two seeds of weights each:
#   the first layer's furthest head: sound 0.0028-0.0030; broken 0.0298,
#   0.0306 / 0.0280, 0.0305 (their nearest head 0.0193).  The limit 0.01:
#   3.3 times the largest sound reading, a third of the smallest broken one.
#   a later layer's whole state: sound 0.0046-0.0085, the largest of a
#   request's seven 0.0076-0.0085 (the bfloat16 stream's rounding of the
#   layers before enters with the layer's input; a later layer's furthest
#   head reads 0.010-0.017 sound, too near the broken layers' 0.048-0.083
#   for a limit with room on both sides); broken 0.0250-0.0349.  The limit
#   0.015: 1.8 times the largest sound reading, 1.7 times under the smallest
#   broken one.
# By their LOGITS the two read 0.075-0.137, under the harness's 0.25 (a sound
# reply's largest gap: 0.063), which is why the state is read at all; every
# other fault of ``power_check.VARIANTS`` reads 0.98-6.8 by its logits.
STATE_LIMIT = 1.0e-2
LATER_STATE_LIMIT = 1.5e-2

"""Plain reference of Phi-4-mini-flash-reasoning's decoder-hybrid-decoder
(``model_type`` phi4flash; SambaY and the gated memory unit,
arXiv:2507.06607; YOCO, arXiv:2405.05254; Mamba, arXiv:2312.00752;
Differential Transformer, arXiv:2410.05258 -- each as known: no network
here).

    x = embed[tok]                         (no multiplier, no positions: NoPE)
    per layer l:  x = x + mixer_l(LN(x));  x = x + W2 (silu(g) * u),
                  [g | u] = W1 LN'(x)      (LayerNorm with weight and bias)
    logits = LN_f(x) embed^T               (tied, no head bias)

The mixer by layer, with L layers, ``mb_per_layer`` 2:

- l even, l <= L/2: **Mamba-1**.  ``[u | z] = W_in h``; ``u = silu(conv(u)
  + b)``, a causal depthwise conv of ``mamba_d_conv`` taps, zeros before
  position 0; ``[delta | B | C] = W_x u``; ``dt = softplus(W_dt delta +
  b_dt)``; ``A = -exp(A_log)``, a value per channel and state dimension;

      S_t = exp(dt_t * A) * S_{t-1} + (dt_t * u_t) (x) B_t
      y_t = S_t . C_t + D * u_t;      out = W_out (y_t * silu(z_t))

  run as a SEQUENTIAL scan over the positions from S_0 = 0.  Layer L/2
  also hands on ``m_t = y_t`` (after the skip term, before the gate): the
  memory.
- l odd, l < L/2: **window attention** over the last ``sliding_window``
  keys, the query's own among them; l = L/2 + 1: **full causal
  attention**, whose keys and values are THE cache of the cross-decoder.
  Both differential: ``[q | k | v] = W h + b``, heads of ``head_dim``;
  diff-head j has the query pair ``(q_2j, q_2j+1)`` and belongs to
  key/value pair ``g = j // (Hq / Hkv)`` with keys ``(k_2g, k_2g+1)`` and
  ONE value ``V_g = [v_2g | v_2g+1]``:

      a_j = softmax(q_2j k_2g^T / sqrt(d)) V_g
            - lambda_l softmax(q_2j+1 k_2g+1^T / sqrt(d)) V_g
      lambda_l = exp(lq1 . lk1) - exp(lq2 . lk2) + linit_l
      linit_l  = 0.8 - 0.6 exp(-0.3 l)
      o_j = RMSNorm(a_j) * w_sub * (1 - linit_l);  out = W_o [o_j] + b_o

  two explicit softmaxes a pair.
- l even, l >= L/2 + 2: **gated memory unit**: ``out = W_out (silu(W_in
  h) * m_t)``, no state, no token mixing.
- l odd, l >= L/2 + 3: **cross attention**: ``q = W_q h + b`` only, keys
  and values layer L/2 + 1's at positions <= t, the same differential
  combination with this layer's own lambda vectors, sub-norm and linit_l.

Every layer runs over every position: no skip of the cross-decoder, no
cache, no kernel, no chunking of the recurrence.  Straight ``jax.numpy``
in float32 under ``default_matmul_precision("highest")``.  What IS in
blocks is the storage, so that a 16,384-position row fits beside a loaded
engine: a layer's weights are cast to float32 a layer at a time, a row's
positions pass a layer ``POSITION_BLOCK`` at a time (a Mamba layer hands
its state and its last conv inputs from block to block: the same
recurrence, position after position), queries attend ``QUERY_BLOCK`` at a
time, and the head reads the vocabulary a slice at a time.

It shares nothing with ``ray_tpu/models/`` but the parameter pytree's key
names and layouts, which is how the program hands over its weights.  The
program keeps its layers in PARTS (``layers``, ``layers_1``, ...: cut
where the pattern changes); a layer's leaf is the n-th row of that leaf
over the parts in order, n the layers before it that have the leaf:

    every layer   attn_norm, attn_norm_bias, mlp_norm, mlp_norm_bias (H),
                  w_gate, w_up (H, F), w_down (F, H)
    mamba         ssm_in (H, 2 Di) [u | z], ssm_conv_w (K, Di) with tap
                  K-1 on the current position, ssm_conv_b (Di), ssm_x (Di,
                  R + 2N) [delta | B | C], ssm_dt (R, Di), ssm_dt_bias
                  (Di), ssm_A_log (N, Di), ssm_D (Di), ssm_out (Di, H)
    attending     wq (H, Hq d), bq, wo (Hq d, H), bo, lambda_q1, lambda_k1,
                  lambda_q2, lambda_k2 (d), sub_norm (2 d); window and
                  full layers also wk, wv (H, Hkv d), bk, bv
    gmu           gmu_in (H, Di), gmu_out (Di, H)
    embed_tokens (V, H), final_norm, final_norm_bias (H)

Departures from the published description: none in the mathematics.  The
published checkpoint fuses ``W_qkv`` and the MLP's ``[g | u]`` and stores
``A_log`` as (Di, N) and the conv weight as (Di, 1, K).

**Gaps are in units of the logits' deviation**, as
``granite_hybrid_decoder``'s: ``teacher_forced_gap`` divides each
position's gap by the standard deviation of the reference's own logits at
that position.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
POSITION_BLOCK = 2048
QUERY_BLOCK = 128
VOCAB_SLICES = 8
EVERY = ("attn_norm", "attn_norm_bias", "mlp_norm", "mlp_norm_bias",
         "w_gate", "w_up", "w_down")
MAMBA = ("ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_x", "ssm_dt",
         "ssm_dt_bias", "ssm_A_log", "ssm_D", "ssm_out")
QUERYING = ("wq", "bq", "wo", "bo", "lambda_q1", "lambda_k1", "lambda_q2",
            "lambda_k2", "sub_norm")
KEYED = ("wk", "wv", "bk", "bv")
GMU = ("gmu_in", "gmu_out")


def layer_kinds(config: Dict[str, Any]) -> List[str]:
    """Which mixer each layer has, from the published keys."""
    L, mb = config["num_hidden_layers"], config["mb_per_layer"]
    if mb != 2 or L % 4:
        raise ValueError("phi4flash_decoder: mb_per_layer 2 and a whole "
                         "number of (mamba, attention) pairs a half")
    half = L // 2
    kinds = []
    for l in range(L):
        if l % 2 == 0:
            kinds.append("mamba" if l <= half else "gmu")
        elif l < half:
            kinds.append("window")
        else:
            kinds.append("attention" if l == half + 1 else "cross")
    return kinds


def _locate(params, name: str, index: int):
    """The ``index``-th of the layers that have leaf ``name``, over the
    parts ``layers``, ``layers_1``, ... in order."""
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
    raise ValueError(f"phi4flash_decoder: no layer {index} of {name}")


def _layer_weights(params, kinds, i: int):
    before = kinds[:i]
    at = {name: i for name in EVERY}
    if kinds[i] == "mamba":
        at.update({name: before.count("mamba") for name in MAMBA})
    elif kinds[i] == "gmu":
        at.update({name: before.count("gmu") for name in GMU})
    else:
        attending = sum(k in ("window", "attention", "cross") for k in before)
        at.update({name: attending for name in QUERYING})
        if kinds[i] != "cross":
            at.update({name: attending - before.count("cross")
                       for name in KEYED})
    return {name: _locate(params, name, n) for name, n in at.items()}


def _layer_norm(x, w, b, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w + b


def _mlp(x, w, eps):
    h = _layer_norm(x, w["mlp_norm"], w["mlp_norm_bias"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


@functools.partial(jax.jit, static_argnums=(4,))
def _mamba_block(x, w, tail, state, eps):
    """A block of positions x (T, H) through a Mamba-1 layer, from the
    ``tail`` (K - 1, Di) of pre-conv inputs and the state (Di, N) the
    positions before it left -> (x, memory (T, Di), tail, state)."""
    w = {k: v.astype(F32) for k, v in w.items()}
    T = x.shape[0]
    Di = w["ssm_D"].shape[0]
    N = w["ssm_A_log"].shape[0]
    R = w["ssm_dt"].shape[0]
    K = w["ssm_conv_w"].shape[0]
    h = _layer_norm(x, w["attn_norm"], w["attn_norm_bias"], eps)
    uz = h @ w["ssm_in"]
    u_in, z = uz[:, :Di], uz[:, Di:]
    padded = jnp.concatenate([tail, u_in], axis=0)
    u = jax.nn.silu(w["ssm_conv_b"] + sum(
        padded[k:k + T] * w["ssm_conv_w"][k] for k in range(K)))
    dbc = u @ w["ssm_x"]
    delta, Bs, Cs = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    dt = jax.nn.softplus(delta @ w["ssm_dt"] + w["ssm_dt_bias"])   # (T, Di)
    A = -jnp.exp(w["ssm_A_log"]).T                                 # (Di, N)

    def position(S, inputs):
        u_t, dt_t, b_t, c_t = inputs
        S = jnp.exp(dt_t[:, None] * A) * S \
            + (dt_t * u_t)[:, None] * b_t[None, :]
        return S, S @ c_t

    state, ys = jax.lax.scan(position, state, (u, dt, Bs, Cs))
    y = ys + w["ssm_D"] * u
    x = x + (y * jax.nn.silu(z)) @ w["ssm_out"]
    return _mlp(x, w, eps), y, padded[T:], state


def _lambdas(w, depth):
    init = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"])) \
        - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"])) + init
    return lam, init


def _softmax_rows(q, k, v, visible, d):
    """softmax(q k^T / sqrt(d)) v over the visible keys: q (T, d), k (S,
    d), v (S, 2d), visible (T, S)."""
    scores = jnp.where(visible, q @ k.T / math.sqrt(d), -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def _differential(q, k, v, visible, w, depth, heads, kv_heads, d, eps):
    """q (T, heads * d) against k, v (S, kv_heads * d): the differential
    heads, each two explicit softmaxes over its pair's one value, normed
    and concatenated (T, heads * d)."""
    lam, init = _lambdas(w, depth)
    T, S = q.shape[0], k.shape[0]
    q = q.reshape(T, heads, d)
    k = k.reshape(S, kv_heads, d)
    v = v.reshape(S, kv_heads // 2, 2 * d)
    per_pair = (heads // 2) // (kv_heads // 2)
    out = []
    for j in range(heads // 2):
        g = j // per_pair
        a = _softmax_rows(q[:, 2 * j], k[:, 2 * g], v[:, g], visible, d) \
            - lam * _softmax_rows(q[:, 2 * j + 1], k[:, 2 * g + 1], v[:, g],
                                  visible, d)
        a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps)
        out.append(a * w["sub_norm"] * (1.0 - init))
    return jnp.concatenate(out, axis=-1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _project_rows(x, w, names, heads, kv_heads, d, eps):
    """LN(x) W + b for the projections ``names`` of (wq, wk, wv)."""
    w = {k: v.astype(F32) for k, v in w.items()}
    h = _layer_norm(x, w["attn_norm"], w["attn_norm_bias"], eps)
    return tuple(h @ w[name] + w["b" + name[1]] for name in names)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11, 12))
def _attend_block(x, q, k, v, first, key_first, w, depth, heads, kv_heads,
                  d, eps, window=0):
    """A block of positions x (T, H), first position ``first``, whose
    queries q (T, heads * d) attend keys k, v (S, ...) at positions
    ``key_first`` on: causal, over the last ``window`` keys where that is
    set; then the output projection and the MLP.  ``QUERY_BLOCK`` queries
    at a time."""
    w = {name: leaf.astype(F32) for name, leaf in w.items()}
    T, S = x.shape[0], k.shape[0]
    key_pos = key_first + jnp.arange(S)

    def queries(args):
        q_block, pos = args
        visible = (key_pos[None, :] <= pos[:, None]) & (key_pos >= 0)[None, :]
        if window:
            visible &= pos[:, None] - key_pos[None, :] < window
        return _differential(q_block, k, v, visible, w, depth, heads,
                             kv_heads, d, eps)

    qb = min(QUERY_BLOCK, T)
    attn = jax.lax.map(queries, (
        q.reshape(T // qb, qb, -1),
        (first + jnp.arange(T)).reshape(T // qb, qb))).reshape(T, -1)
    return _mlp(x + attn @ w["wo"] + w["bo"], w, eps)


@functools.partial(jax.jit, static_argnums=(3,))
def _gmu_block(x, memory, w, eps):
    w = {k: v.astype(F32) for k, v in w.items()}
    h = _layer_norm(x, w["attn_norm"], w["attn_norm_bias"], eps)
    x = x + (jax.nn.silu(h @ w["gmu_in"]) * memory) @ w["gmu_out"]
    return _mlp(x, w, eps)


def _padded(tokens: np.ndarray) -> np.ndarray:
    """The row lengthened with zeros to whole blocks (what follows a
    position never reaches it)."""
    s = len(tokens)
    block = POSITION_BLOCK if s > POSITION_BLOCK else QUERY_BLOCK
    return np.concatenate([tokens, np.zeros(-s % block, np.int32)])


def _hidden(params, tokens: np.ndarray, config: Dict[str, Any]):
    """For ONE row of tokens (S,), whole blocks: the last layer's output
    (S, H) float32."""
    kinds = layer_kinds(config)
    eps = float(config["layer_norm_eps"])
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    d = config["hidden_size"] // heads
    window = config["sliding_window"]
    S = len(tokens)
    T = min(POSITION_BLOCK, S)
    blocks = range(0, S, T)
    x = params["embed_tokens"][jnp.asarray(tokens)].astype(F32)
    memory = shared = None
    for i, kind in enumerate(kinds):
        w = _layer_weights(params, kinds, i)
        sizes = (heads, kv_heads, d, eps)
        if kind == "mamba":
            Di, N = w["ssm_D"].shape[0], w["ssm_A_log"].shape[0]
            tail = jnp.zeros((w["ssm_conv_w"].shape[0] - 1, Di), F32)
            state = jnp.zeros((Di, N), F32)
            out, ys = [], []
            for b in blocks:
                xb, y, tail, state = _mamba_block(x[b:b + T], w, tail,
                                                  state, eps)
                out.append(xb)
                ys.append(y)
            x = jnp.concatenate(out)
            if i == len(kinds) // 2:
                memory = jnp.concatenate(ys)
            del out, ys
        elif kind == "gmu":
            x = jnp.concatenate([_gmu_block(x[b:b + T], memory[b:b + T], w,
                                            eps) for b in blocks])
        elif kind == "window":
            q, k, v = _project_rows(x, w, ("wq", "wk", "wv"), *sizes)
            # a block's keys: its own and the ``reach`` positions before
            reach = -(-(window - 1) // QUERY_BLOCK) * QUERY_BLOCK
            front = jnp.zeros((reach, k.shape[1]), F32)
            k, v = jnp.concatenate([front, k]), jnp.concatenate([front, v])
            x = jnp.concatenate([
                _attend_block(x[b:b + T], q[b:b + T], k[b:b + T + reach],
                              v[b:b + T + reach], b, b - reach, w, i,
                              *sizes, window) for b in blocks])
        else:
            if kind == "attention":
                q, *shared = _project_rows(x, w, ("wq", "wk", "wv"), *sizes)
            else:
                q, = _project_rows(x, w, ("wq",), *sizes)
            x = jnp.concatenate([
                _attend_block(x[b:b + T], q[b:b + T], *shared, b, 0, w, i,
                              *sizes) for b in blocks])
    return x


@functools.partial(jax.jit, static_argnums=(4,))
def _head_slice(x, norm_w, norm_b, rows, eps):
    """Logits (T, v) of the vocabulary rows ``rows`` (v, H)."""
    return _layer_norm(x, norm_w.astype(F32), norm_b.astype(F32), eps) \
        @ rows.astype(F32).T


def _row_logits(params, x, config):
    """(T, V) float32 logits of hidden rows x (T, H), a slice of the
    vocabulary at a time."""
    if not config["tie_word_embeddings"]:
        raise ValueError("phi4flash_decoder: tied head only")
    table = params["embed_tokens"]
    step = -(-table.shape[0] // VOCAB_SLICES)
    return jnp.concatenate([
        _head_slice(x, params["final_norm"], params["final_norm_bias"],
                    table[i:i + step], float(config["layer_norm_eps"]))
        for i in range(0, table.shape[0], step)], axis=-1)


def logits(params: Dict[str, Any], tokens, config: Dict[str, Any]):
    """(B, S, V) float32 logits for ``tokens`` (B, S) int32.  ``config``
    is the configuration file's dict (published key names).  The whole
    vocabulary at every position: for short rows."""
    tokens = np.asarray(tokens, np.int32)
    with jax.default_matmul_precision("highest"):
        return jnp.stack([
            _row_logits(params, _hidden(params, _padded(row),
                                        config)[:len(row)], config)
            for row in tokens])


@jax.jit
def _gap(lg, nxt):
    gap = lg.max(-1) - jnp.take_along_axis(lg, nxt[:, None], -1)[:, 0]
    return gap / lg.std(-1)


def teacher_forced_gap(params: Dict[str, Any], prompt, emitted,
                       config: Dict[str, Any], pad_to: int = 0) -> np.ndarray:
    """For a greedy decoder's ``emitted`` tokens after ``prompt``: at each
    emitted position, the reference's top logit minus the reference's
    logit of the token that was emitted (0 where they agree), IN UNITS OF
    THE STANDARD DEVIATION of the reference's logits at that position, one
    full forward pass over prompt + emitted.  ``pad_to`` lengthens the row
    with zeros to one compiled shape: causal attention and a recurrence
    that runs forward keep what follows a position from reaching it.  The
    head reads the emitted positions alone, ``QUERY_BLOCK`` at a time."""
    seq = list(prompt) + list(emitted)
    seq = _padded(np.asarray(seq + [0] * max(0, pad_to - len(seq)),
                             np.int32))
    first, n = len(prompt) - 1, len(emitted)
    with jax.default_matmul_precision("highest"):
        x = _hidden(params, seq, config)
        nxt = jnp.asarray(np.roll(seq, -1))
        gaps = []
        for at in range(first, first + n, QUERY_BLOCK):
            at = min(at, len(seq) - QUERY_BLOCK)     # a whole block, inside
            gaps.append((at, np.asarray(_gap(
                _row_logits(params, x[at:at + QUERY_BLOCK], config),
                nxt[at:at + QUERY_BLOCK]))))
    out = np.zeros(len(seq), np.float32)
    for at, gap in gaps:
        out[at:at + QUERY_BLOCK] = gap
    return out[first:first + n]

"""KDA mixer: a gated DELTA-RULE linear attention (Kimi Linear,
arXiv:2510.26692; the public flash-linear-attention ``kda`` layer) for the
layers of a hybrid model that keep a MATRIX-valued state a head
(``LlamaConfig`` kind ``kda``), beside ``mamba2.py`` / ``mamba1.py`` /
``shortconv.py``.

Per layer and token, with ``H = kda_heads`` heads of ``d = kda_head_dim``
(keys and values alike), a depthwise causal conv of ``K = kda_conv`` taps
over the ``3 H d`` channels of ``[q | k | v]`` and low-rank gates of rank
``R = kda_gate_rank``:

    [q~ | k~ | v~] = silu(conv(W_qkv x))                 no bias
    q = l2norm_head(q~) d^-1/2;  k = l2norm_head(k~);  v = v~
    [f | z | beta] = W_low x                             (R | R | H)
    g = -exp(A_log_h) softplus(W_g2 f + dt_bias)         (H, d) <= 0
    b = 2 sigmoid(beta)                                  (H,) in (0, 2)
    S_t = (I - b k k^T) Diag(exp g) S_{t-1} + b k v^T    (d, d) a head
    o_t = S_t^T q
    out = W_o [rmsnorm_head(o_t; w) * sigmoid(W_z2 z)]

The decay is a value a CHANNEL of the key and sits inside the rank-1
correction's dot products: Mamba-2's matmul form (one scalar decay a head,
no ``k k^T S`` term: ``mamba2.ssd_chunked``) and ``ops/ssm_state_update.py``
do not compute it.  ``b`` reaches past 1, so ``I - b k k^T`` has an
eigenvalue in [-1, 1] (``kda_allow_neg_eigval``).

Two forms of the same recurrence.  ``prefill`` runs it in chunks of
``kda_chunk`` positions in the WY / UT form (``chunk_rule``): with ``G_i``
the log-decay summed from the chunk's start to ``i``,

    A_ij = b_i sum_c k_ic k_jc e^(G_ic - G_jc)   j < i
    (I + A) [W | Y] = Diag(b) [V | e^G (.) K]
    U = W - Y S;   o_i = S^T (e^G_i (.) q_i) + sum_{j<=i} P_ij u_j
    S <- Diag(e^G_C) S + (e^(G_C - G) (.) K)^T U

``P`` as ``A`` with ``q_i`` for ``k_i`` and the diagonal kept.  Factored as
``(k_i e^G_i) . (k_j e^-G_j)`` the products overflow float32 inside one
chunk, so a decay enters only as ``exp`` of a difference that is <= 0: in
sub-blocks of ``_SUB`` rows, a sub-block against the ones before it through
its own first row (two factors, both <= 1, a matmul), against itself by the
``(_SUB, _SUB, d)`` broadcast (``_decayed_products``).  Everything that
does not read the state is computed for ``_BLOCK`` positions at once; the
state walks the chunks in order.  At a state Mosaic tiles (``d`` whole
128-lane tiles: the published widths) a block's chunks are ONE kernel
(``ops/kda_chunk.py``: the same form with everything between q, k, v, g and
``o`` in VMEM); ``chunk_rule`` here is XLA's form, which a toy preset's
shape keeps and the tests hold the kernel against.  A padded position takes
``g = 0`` and ``b = 0``: it neither
decays the state nor writes it, so a row's state is that of ITS OWN last
real position, and its conv state its last ``K - 1`` real pre-conv inputs.

``decode`` advances every slot's state by one token in place in the stacked
states the serving loops carry (``ops/kda_state_update.py``: an advancing
slot's state read once and written once where it lies, another's neither).

Float32: the state as stored (``ssm_state_dtype``), the chunk rule and
everything between the projections.  The projections take the model's
compute type with float32 accumulation.

Layouts: the state ``(Lk, B, H, d, d)``, keys on the sublanes and values
on the lanes; the conv state ``(Lk, K - 1, B, 3 H d)`` and the conv weight
``(Lk, K, 3 H d)`` as ``mamba2``'s.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

# Rows of a sub-block (a chunk is whole sub-blocks) and positions whose
# state-free part is computed at once (whole chunks): float32 temporaries
# of ``_BLOCK x H d`` are 32 MB each at the published widths, whatever the
# prompt's length.
_SUB = 16
_BLOCK = 1024
_HIGHEST = jax.lax.Precision.HIGHEST


def dims(c):
    """(H d, the conv's channels, the width of the low-rank in-projection)."""
    hd = c.kda_heads * c.kda_head_dim
    return hd, 3 * hd, 2 * c.kda_gate_rank + c.kda_heads


def param_axes(c) -> Dict[str, tuple]:
    return {
        "kda_qkv": ("layers", "embed", "mlp"),
        "kda_conv_w": ("layers", None, "mlp"),
        "kda_low": ("layers", "embed", None),
        "kda_g2": ("layers", None, "mlp"),
        "kda_z2": ("layers", None, "mlp"),
        "kda_A_log": ("layers", None),
        "kda_dt_bias": ("layers", "mlp"),
        "kda_norm": ("layers", None),
        "kda_o": ("layers", "mlp", "embed"),
    }


def init_params(key: jax.Array, c, layers: int, dtype: Any,
                dense) -> Dict[str, jax.Array]:
    """The Mamba convention for the leaves no matmul owns, as the public
    layer draws them: ``A_log = log U[1, 16]`` a head, ``dt_bias`` the
    inverse softplus of a ``dt`` drawn log-uniform in [1e-3, 1e-1] a
    channel (so that no term idles), the head norm at 1; the projections
    and the conv's taps by ``dense``."""
    D, H, R = c.hidden_size, c.kda_heads, c.kda_gate_rank
    hd, conv_dim, low = dims(c)
    ks = jax.random.split(key, 8)
    dt = jnp.exp(jax.random.uniform(
        ks[6], (layers, hd), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "kda_qkv": dense(ks[0], (layers, D, conv_dim), D),
        "kda_conv_w": dense(ks[1], (layers, c.kda_conv, conv_dim),
                            c.kda_conv),
        "kda_low": dense(ks[2], (layers, D, low), D),
        "kda_g2": dense(ks[3], (layers, R, hd), R),
        "kda_z2": dense(ks[4], (layers, R, hd), R),
        "kda_A_log": jnp.log(jax.random.uniform(
            ks[5], (layers, H), jnp.float32, 1.0, 16.0)).astype(dtype),
        "kda_dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "kda_norm": jnp.ones((layers, c.kda_head_dim), dtype),
        "kda_o": dense(ks[7], (layers, hd, D), hd),
    }


def init_state(c, layers: int, batch: int) -> Dict[str, jax.Array]:
    """Zero recurrent and conv states of ``layers`` KDA layers for
    ``batch`` slots."""
    d = c.kda_head_dim
    return {
        "ssm": jnp.zeros((layers, batch, c.kda_heads, d, d),
                         c.ssm_state_dtype),
        "conv": jnp.zeros((layers, c.kda_conv - 1, batch, dims(c)[1]),
                          c.dtype),
    }


@jax.named_scope("ssm_proj")
def _project(h: jax.Array, layer, c):
    """(the conv's input [q | k | v] in the compute type, the low-rank
    in-projection [f | z | beta] float32)."""
    from ray_tpu.models.llama import matmul

    return (matmul(h, layer["kda_qkv"].astype(c.dtype)),
            matmul(h, layer["kda_low"].astype(c.dtype), jnp.float32))


def _conv_act(window, layer):
    """``silu`` of the depthwise conv over ``window`` (K of (..., 3 H d),
    oldest tap first), float32."""
    w = layer["kda_conv_w"].astype(jnp.float32)
    acc = window[0].astype(jnp.float32) * w[0]
    for k in range(1, w.shape[0]):
        acc = acc + window[k].astype(jnp.float32) * w[k]
    return jax.nn.silu(acc)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _heads(qkv, low, layer, c, live):
    """The recurrence's inputs of the conv's output ``qkv`` (..., 3 H d)
    float32 and ``low`` (..., 2 R + H): ``(q, k, v, g (..., H, d), b (...,
    H), the output gate's low-rank part z)``, float32, ``g`` and ``b`` 0
    where not ``live`` (..., 1)."""
    from ray_tpu.models.llama import matmul

    H, d, R = c.kda_heads, c.kda_head_dim, c.kda_gate_rank
    f32 = jnp.float32
    q, k, v = (x.reshape(x.shape[:-1] + (H, d))
               for x in jnp.split(qkv, 3, axis=-1))
    f = matmul(low[..., :R].astype(c.dtype), layer["kda_g2"].astype(c.dtype),
               f32)
    g = jax.nn.softplus(f + layer["kda_dt_bias"].astype(f32))
    g = g.reshape(g.shape[:-1] + (H, d)) \
        * -jnp.exp(layer["kda_A_log"].astype(f32))[:, None]
    b = 2.0 * jax.nn.sigmoid(low[..., 2 * R:])
    return (_l2norm(q) * d ** -0.5, _l2norm(k), v,
            jnp.where(live[..., None], g, 0.0), jnp.where(live, b, 0.0),
            low[..., R:2 * R])


def _gated_norm(o, z, layer, c):
    """``rmsnorm_head(o; w) * sigmoid(W_z2 z)`` in the compute type: o
    (..., H, d) float32 -> (..., H d)."""
    from ray_tpu.models.llama import matmul, rms_norm

    gate = matmul(z.astype(c.dtype), layer["kda_z2"].astype(c.dtype),
                  jnp.float32)
    o = rms_norm(o, layer["kda_norm"], c.norm_eps)
    o = o.reshape(o.shape[:-2] + (-1,)).astype(jnp.float32)
    return (o * jax.nn.sigmoid(gate)).astype(c.dtype)


@jax.named_scope("ssm_out")
def _project_out(y, layer, c):
    from ray_tpu.models.llama import matmul

    return matmul(y, layer["kda_o"].astype(c.dtype))


def _decayed_products(q, k, G, sub: int):
    """Of one chunk's q, k and G (..., C, d), G the log-decay summed from
    the chunk's start (falling along C): ``M_x[i, j] = sum_c x_ic k_jc
    exp(G_ic - G_jc)`` for x = k and x = q, stacked (2, ..., C, C), exact
    where j <= i and zero above that diagonal.  Every exponent is <= 0."""
    C, d = G.shape[-2:]
    n = C // sub
    lead = G.shape[:-2]

    def blocks(x):
        return x.reshape(lead + (n, sub, d))

    Gb = blocks(G)
    first = Gb[..., :1, :]                         # a sub-block's first row
    rows = jnp.arange(C, dtype=jnp.int32)
    # before sub-block I: j < I * sub, through e^(G_i - G_first(I)) and
    # e^(G_first(I) - G_j)
    before = rows[None, :] < (jnp.arange(n, dtype=jnp.int32) * sub)[:, None]
    to_first = jnp.exp(jnp.where(
        before[..., None], first - G[..., None, :, :], -jnp.inf))
    kj = k[..., None, :, :] * to_first                      # (.., n, C, d)
    from_first = jnp.exp(Gb - first)
    # inside sub-block I: the (sub, sub, d) broadcast
    inside = jnp.exp(jnp.where(
        jnp.tril(jnp.ones((sub, sub), bool))[..., None],
        Gb[..., :, None, :] - Gb[..., None, :, :], -jnp.inf))
    kb = blocks(k)
    eye = jnp.eye(n, dtype=G.dtype)[:, None, :, None]
    # k and q side by side: each decay is computed once and read once
    xb = jnp.stack([kb, blocks(q)])
    off = jnp.einsum("x...nic,...njc->x...nij", xb * from_first, kj,
                     precision=_HIGHEST)
    diag = jnp.sum(xb[..., :, None, :] * (kb[..., None, :, :] * inside), -1)
    full = off.reshape((2,) + lead + (n, sub, n, sub)) \
        + diag[..., :, :, None, :] * eye
    return full.reshape((2,) + lead + (C, C))


def chunk_rule(q, k, v, g, b, state, chunk: int):
    """The recurrence over T positions (whole chunks) from ``state``, in
    the WY / UT form.  q, k, v, g (N, T, H, d) and b (N, T, H) float32, g
    and b 0 at padded positions; state (N, H, d, d) float32.  Returns (o
    (N, T, H, d) float32, the state after the last position)."""
    N, T, H, d = q.shape
    C, nc = chunk, T // chunk

    def chunks(x):                     # (N, T, H, ...) -> (nc, N, H, C, ...)
        x = x.reshape((N, nc, C, H) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    b = chunks(b)[..., None]                                # (.., C, 1)
    G = jnp.cumsum(g, axis=-2)
    Mk, Mq = _decayed_products(q, k, G, math.gcd(_SUB, C))
    rows = jnp.arange(C, dtype=jnp.int32)
    A = jnp.where(rows[:, None] > rows[None, :], b * Mk, 0.0)
    grown = jnp.exp(G)
    wy = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(C, dtype=A.dtype),
        jnp.concatenate([b * v, b * grown * k], -1), lower=True,
        unit_diagonal=True)
    to_end = jnp.exp(G[..., -1:, :] - G)

    def one(S, xs):
        W, Y, qg, P, kend, last = xs
        U = W - jnp.einsum("nhck,nhkv->nhcv", Y, S, precision=_HIGHEST)
        o = jnp.einsum("nhck,nhkv->nhcv", qg, S, precision=_HIGHEST) \
            + jnp.einsum("nhij,nhjv->nhiv", P, U, precision=_HIGHEST)
        S = last[..., None] * S \
            + jnp.einsum("nhck,nhcv->nhkv", kend, U, precision=_HIGHEST)
        return S, o

    state, o = jax.lax.scan(
        one, state, (wy[..., :d], wy[..., d:], q * grown, Mq, k * to_end,
                     grown[..., -1, :]))
    # (nc, N, H, C, d) -> (N, T, H, d)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)
    return o.reshape(N, T, H, d), state


def _block_len(P: int, chunk: int) -> int:
    """Positions ``prefill`` takes at once: whole chunks, at most
    ``_BLOCK`` (one chunk at least), as few blocks as hold ``P``."""
    per = max(1, _BLOCK // chunk)
    chunks = -(-P // chunk)
    blocks = -(-chunks // per)
    return -(-chunks // blocks) * chunk


def padded_len(P: int, chunk: int) -> int:
    """Positions a row of ``P`` goes through the chunked rule: whole
    blocks of ``_block_len``."""
    T = _block_len(P, chunk)
    return -(-P // T) * T


def prefill(h: jax.Array, layer, c, lengths: Optional[jax.Array]):
    """The mixer over right-padded prompts from empty states.

    h (G, P, D) normed hidden states; lengths (G,) real lengths (None:
    every position is real).  Returns (out (G, P, D), (state (G, H, d, d)
    in the state's storage type, conv state (K - 1, G, 3 H d))), both as
    of each row's last real position."""
    from ray_tpu.ops.kda_chunk import kda_chunk

    G, P, _ = h.shape
    H, d, K = c.kda_heads, c.kda_head_dim, c.kda_conv
    hd = dims(c)[0]
    if lengths is None:
        lengths = jnp.full((G,), P, jnp.int32)
    qkv_in, low = _project(h, layer, c)
    T = _block_len(P, c.kda_chunk)
    nb = -(-P // T)
    # position p lies at row p + K - 1: a block reads its own rows and the
    # K - 1 before them
    padded = jnp.pad(qkv_in, ((0, 0), (K - 1, nb * T - P), (0, 0)))
    low = jnp.pad(low, ((0, 0), (0, nb * T - P), (0, 0)))
    with jax.named_scope("kda_gates"):
        # padded[i] is position i - (K - 1): the last K - 1 real inputs
        taps = lengths[:, None] \
            + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        conv_state = jnp.take_along_axis(
            padded, taps[:, :, None], axis=1).transpose(1, 0, 2)
        conv_state = conv_state.astype(c.dtype)

    def block(S, i):
        at = i * T
        with jax.named_scope("kda_gates"):
            window = jax.lax.dynamic_slice_in_dim(padded, at, T + K - 1, 1)
            qkv = _conv_act([window[:, j:j + T] for j in range(K)], layer)
            live = ((at + jnp.arange(T, dtype=jnp.int32))[None, :]
                    < lengths[:, None])[..., None]
            q, k, v, g, b, z = _heads(
                qkv, jax.lax.dynamic_slice_in_dim(low, at, T, 1), layer, c,
                live)
        with jax.named_scope("kda_chunk"):
            o, S = kda_chunk(q, k, v, g, b, S, c.kda_chunk)
        with jax.named_scope("kda_gates"):
            return S, _gated_norm(o, z, layer, c)

    state, y = jax.lax.scan(
        block, jnp.zeros((G, H, d, d), jnp.float32),
        jnp.arange(nb, dtype=jnp.int32))
    y = jnp.moveaxis(y, 0, 1).reshape(G, nb * T, hd)[:, :P]
    with jax.named_scope("kda_chunk"):
        state = state.astype(c.ssm_state_dtype)
    return _project_out(y, layer, c), (state, conv_state)


def decode(h: jax.Array, layer, c, ssm: jax.Array, conv: jax.Array,
           m: jax.Array, active: jax.Array):
    """One token a slot through KDA layer ``m`` of the stacked states.

    h (B, 1, D); ssm (Lk, B, H, d, d) and conv (Lk, K - 1, B, 3 H d) are
    the WHOLE stacks (the serving loops' carry): layer ``m`` is read and
    written in place.  A slot that is not ``active`` keeps both states as
    they are.  Returns (out (B, 1, D), ssm, conv)."""
    from ray_tpu.ops.kda_state_update import kda_state_update

    qkv_in, low = _project(h[:, 0], layer, c)
    with jax.named_scope("kda_gates"):
        old = jax.lax.dynamic_index_in_dim(conv, m, 0, keepdims=False)
        window = jnp.concatenate([old, qkv_in[None].astype(conv.dtype)], 0)
        qkv = _conv_act(window, layer)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(active[None, :, None], window[1:], old), m, 0)
        q, k, v, g, b, z = _heads(qkv, low, layer, c, active[:, None])
        decay = jnp.exp(g)
    with jax.named_scope("kda_state_update"):
        ssm, o = kda_state_update(ssm, m, active, decay, q, k, v, b)
    with jax.named_scope("kda_gates"):
        y = _gated_norm(o, z, layer, c)
    return _project_out(y, layer, c)[:, None], ssm, conv

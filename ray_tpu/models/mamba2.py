"""Mamba-2 mixer (state-space duality, arXiv:2405.21060) for the layers
of a hybrid model that are not attention (``LlamaConfig.layer_pattern``).

Per layer and token, with ``nh`` heads of ``hd`` channels, ``G`` groups
of ``N`` state dimensions (``ssm_groups``: head ``j`` reads the B and C of
group ``j // (nh / G)``; Granite 4 has one group shared by all heads,
Nemotron-H eight), and a depthwise causal conv of ``K`` taps over
``conv_dim = nh * hd + 2 * G * N`` channels:

    [z | xBC | dt] = W_in h                    (nh*hd | conv_dim | nh)
    xBC = silu(conv(xBC) + b);  x, B, C = split(xBC)      B, C: (G, N)
    dt  = softplus(dt + dt_bias);  A = -exp(A_log)            per head
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t              (nh, hd, N)
    y_t = S_t C_t + D x_t
    out = W_out (RMSNorm(y * silu(z)) * w)     a GROUP's nh*hd/G channels
                                               at a time (G = 1: the whole)

Two forms of the same recurrence.  ``prefill`` runs it over a prompt in
chunks of ``ssm_chunk`` positions (the matmul form: inside a chunk a
masked ``(C B^T) * decay`` matrix, between chunks the state) and hands
back each row's state at ITS OWN last real position: a padded position
takes ``dt = 0``, so it neither decays the state nor feeds it, and a
row's conv state is its last ``K - 1`` real pre-conv inputs (zeros
before position 0).  ``decode`` advances every slot's state by one token
in place: the states of all layers are the carry of the serving loops
(``llama_serve.decode_step``; the update itself is
``ops/ssm_state_update.py``), a row that is not ``active`` is neither
read nor written and keeps its conv window: its state does not advance.

The recurrence's arithmetic is float32 whatever type the state is stored
in (``ssm_state_dtype``), and so is everything between the projections:
the conv and its ``silu``, ``dt`` (its projection is read out in
float32: a rounding of it is multiplied by A and exponentiated), the
gate and the gated norm.  The projections and the two chunk matmuls
take the model's compute type with float32 accumulation, as every other
matmul of ``models/llama.py`` does.

Layouts chosen for the TPU's (sublane, lane) tiles: the recurrent state
is ``(Lm, B, N, HD)`` with the ``HD = nh x hd`` channels on the lanes
(``ops/ssm_state_update.py`` says why); the conv state ``(Lm, K - 1, B,
conv_dim)`` and the conv weight ``(Lm, K, conv_dim)`` keep the channels
minor (the published checkpoint's ``(conv_dim, 1, K)`` would pad 4 taps
to 128 lanes).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

def dims(c) -> Tuple[int, int, int]:
    """(d_inner, conv_dim, width of the in-projection)."""
    d_inner = c.ssm_heads * c.ssm_head_dim
    conv_dim = d_inner + 2 * c.ssm_groups * c.ssm_state
    return d_inner, conv_dim, d_inner + conv_dim + c.ssm_heads


def param_axes(c) -> Dict[str, tuple]:
    return {
        "ssm_in": ("layers", "embed", "mlp"),
        "ssm_dt": ("layers", "embed", None),
        "ssm_conv_w": ("layers", None, "mlp"),
        "ssm_conv_b": ("layers", "mlp"),
        "ssm_dt_bias": ("layers", None),
        "ssm_A_log": ("layers", None),
        "ssm_D": ("layers", None),
        "ssm_norm": ("layers", "mlp"),
        "ssm_out": ("layers", "mlp", "embed"),
    }


def init_params(key: jax.Array, c, layers: int, dtype: Any,
                dense) -> Dict[str, jax.Array]:
    """The Mamba-2 convention for the leaves no matmul owns: ``A_log =
    log U[1, 16]``, ``dt_bias`` the inverse softplus of a ``dt`` drawn
    log-uniform in [1e-3, 1e-1], ``D = 1``, the norm at 1, the conv
    fan-in scaled with a zero bias; the two projections by ``dense``
    (``llama.init_dense`` under the caller's dtype)."""
    d_inner, conv_dim, in_dim = dims(c)
    nh, K = c.ssm_heads, c.ssm_conv
    k_in, k_conv, k_dt, k_a, k_out, k_dtw = jax.random.split(key, 6)
    dt = jnp.exp(jax.random.uniform(
        k_dt, (layers, nh), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "ssm_in": dense(k_in, (layers, c.hidden_size, in_dim - nh),
                        c.hidden_size),
        "ssm_dt": dense(k_dtw, (layers, c.hidden_size, nh), c.hidden_size),
        "ssm_conv_w": dense(k_conv, (layers, K, conv_dim), K),
        "ssm_conv_b": jnp.zeros((layers, conv_dim), dtype),
        "ssm_dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "ssm_A_log": jnp.log(jax.random.uniform(
            k_a, (layers, nh), jnp.float32, 1.0, 16.0)).astype(dtype),
        "ssm_D": jnp.ones((layers, nh), dtype),
        "ssm_norm": jnp.ones((layers, d_inner), dtype),
        "ssm_out": dense(k_out, (layers, d_inner, c.hidden_size), d_inner),
    }


def init_state(c, layers: int, batch: int) -> Dict[str, jax.Array]:
    """Zero recurrent and conv states of ``layers`` Mamba layers for
    ``batch`` slots."""
    _d_inner, conv_dim, _ = dims(c)
    return {
        "ssm": jnp.zeros((layers, batch, c.ssm_state,
                          c.ssm_heads * c.ssm_head_dim), c.ssm_state_dtype),
        "conv": jnp.zeros((layers, c.ssm_conv - 1, batch, conv_dim),
                          c.dtype),
    }


@jax.named_scope("ssm_proj")
def _project(h: jax.Array, layer, c):
    """z, the conv's input xBC, the raw dt.  The published in-projection
    is ONE matrix ``[z | xBC | dt]``; its dt columns are a leaf of their
    own here (``ssm_dt``), because a stack whose rows are not whole lanes
    (8,512 = 66.5 x 128) is copied whole before a loop reads a layer of
    it (1.26 GB at granite-4.0-h-micro's widths, seen in the HLO compiled
    for a v5e)."""
    from ray_tpu.models.llama import matmul

    d_inner, _conv_dim, _ = dims(c)
    zxbc = matmul(h, layer["ssm_in"].astype(c.dtype))
    return (zxbc[..., :d_inner], zxbc[..., d_inner:],
            matmul(h, layer["ssm_dt"].astype(c.dtype), jnp.float32))


@jax.named_scope("ssm_conv")
def _conv_act(window, layer):
    """``silu`` of the depthwise conv over ``window`` ((K, ..., conv_dim),
    oldest tap first), float32."""
    w = layer["ssm_conv_w"].astype(jnp.float32)
    acc = layer["ssm_conv_b"].astype(jnp.float32)
    for k in range(w.shape[0]):
        acc = acc + window[k].astype(jnp.float32) * w[k]
    return jax.nn.silu(acc)


def _dt_a(dt_raw, layer, live):
    """(dt in float32 with 0 where not ``live``, A per head)."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + layer["ssm_dt_bias"].astype(jnp.float32))
    return (jnp.where(live, dt, 0.0),
            -jnp.exp(layer["ssm_A_log"].astype(jnp.float32)))


@jax.named_scope("ssm_out")
def _gated_out(y, z, layer, c):
    """``W_out (RMSNorm(y * silu(z)) * w)``, the norm over each group's
    channels (one group: over all of them): y float32 (..., nh, hd)."""
    from ray_tpu.models.llama import matmul, rms_norm

    d_inner = c.ssm_heads * c.ssm_head_dim
    by_group = y.shape[:-2] + (c.ssm_groups, d_inner // c.ssm_groups)
    y = rms_norm(
        y.reshape(by_group) * jax.nn.silu(
            z.astype(jnp.float32)).reshape(by_group),
        layer["ssm_norm"].reshape(by_group[-2:]), c.norm_eps)
    y = y.reshape(y.shape[:-2] + (d_inner,))
    return matmul(y.astype(c.dtype), layer["ssm_out"].astype(c.dtype))


def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, chunk: int):
    """The recurrence over P positions from a zero state, in chunks.

    x (G, P, nh, hd) and B, C (G, P, R, N), R groups of state dimensions
    (head j reads group j // (nh / R)), in the compute type; dt (G, P, nh)
    float32, 0 at padded positions; A (nh,) float32, negative.
    Returns (y (G, P, nh, hd) float32 without the ``D x`` term, the state
    after the last position (G, nh, hd, N) float32)."""
    G, P, nh, hd = x.shape
    R, N = B.shape[-2:]
    per = nh // R                        # heads a group
    Q = min(chunk, P)
    if P % Q:
        raise ValueError(f"{P} positions are not whole chunks of {Q}")
    nc = P // Q
    f32 = jnp.float32
    xc = x.reshape(G, nc, Q, nh, hd)
    Bc, Cc = B.reshape(G, nc, Q, R, N), C.reshape(G, nc, Q, R, N)
    dth = dt.reshape(G, nc, Q, nh).transpose(0, 1, 3, 2)   # (G, nc, nh, Q)
    acum = jnp.cumsum(dth * A[None, None, :, None], axis=-1)
    # Inside a chunk: position i reads j <= i through exp(sum of a over
    # (j, i]) -- one (Q, Q) matrix a head -- times C_i . B_j, one (Q, Q)
    # matrix a GROUP, shared by its heads.
    seg = acum[..., :, None] - acum[..., None, :]      # (G, nc, nh, Qi, Qj)
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("gcirn,gcjrn->gcrij", Cc, Bc,
                        preferred_element_type=f32)
    mix = jnp.repeat(scores, per, axis=2) * decay * dth[..., None, :]
    y = jnp.einsum("gchij,gcjhd->gcihd", mix.astype(x.dtype), xc,
                   preferred_element_type=f32)
    # What a chunk adds to the state by its end.
    to_end = jnp.exp(acum[..., -1:] - acum) * dth          # (G, nc, nh, Q)
    xw = (xc.astype(f32) * to_end.transpose(0, 1, 3, 2)[..., None]
          ).astype(x.dtype)
    states = jnp.einsum("gcjrhd,gcjrn->gcrhdn",
                        xw.reshape(G, nc, Q, R, per, hd), Bc,
                        preferred_element_type=f32
                        ).reshape(G, nc, nh, hd, N)
    if nc == 1:
        return y.reshape(G, P, nh, hd), states[:, 0]
    # Between chunks: the state entering chunk c, a scan over the chunks.
    chunk_decay = jnp.exp(acum[..., -1])                   # (G, nc, nh)

    def carry_state(s, decay_and_add):
        d, add = decay_and_add
        return d[..., None, None] * s + add, s

    final, entering = jax.lax.scan(
        carry_state, jnp.zeros((G, nh, hd, N), f32),
        (chunk_decay.transpose(1, 0, 2), states.transpose(1, 0, 2, 3, 4)))
    y_in = jnp.einsum("gcirn,cgrhdn->gcirhd", Cc.astype(f32),
                      entering.reshape(nc, G, R, per, hd, N),
                      preferred_element_type=f32
                      ).reshape(G, nc, Q, nh, hd)
    y = y + y_in * jnp.exp(acum).transpose(0, 1, 3, 2)[..., None]
    return y.reshape(G, P, nh, hd), final


def prefill(h: jax.Array, layer, c, lengths: Optional[jax.Array]):
    """The mixer over right-padded prompts from empty states.

    h (G, P, H) normed hidden states; lengths (G,) real lengths (None:
    every position is real).  Returns (out (G, P, H), (state (G, N, HD)
    in the state's storage type, conv state (K - 1, G, conv_dim))), both
    as of each row's last real position."""
    G, P, _ = h.shape
    nh, hd, N, K = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_conv
    R = c.ssm_groups
    d_inner = nh * hd
    if lengths is None:
        lengths = jnp.full((G,), P, jnp.int32)
    z, xbc_in, dt_raw = _project(h, layer, c)
    with jax.named_scope("ssm_conv"):
        padded = jnp.pad(xbc_in, ((0, 0), (K - 1, 0), (0, 0)))
        # the chunk matmuls' operands, in the compute type
        xbc = _conv_act([padded[:, k:k + P] for k in range(K)],
                        layer).astype(c.dtype)
        # padded[i] is position i - (K - 1): the last K - 1 real inputs
        taps = lengths[:, None] \
            + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        conv_state = jnp.take_along_axis(
            padded, taps[:, :, None], axis=1).transpose(1, 0, 2)
    with jax.named_scope("ssm_scan"):
        x = xbc[..., :d_inner].reshape(G, P, nh, hd)
        B, C = (xbc[..., at:at + R * N].reshape(G, P, R, N)
                for at in (d_inner, d_inner + R * N))
        live = (jnp.arange(P, dtype=jnp.int32)[None, :]
                < lengths[:, None])[..., None]
        dt, A = _dt_a(dt_raw, layer, live)
        y, state = ssd_chunked(x, dt, A, B, C, c.ssm_chunk)
        y = y + layer["ssm_D"].astype(jnp.float32)[:, None] * x.astype(
            jnp.float32)
        state = state.transpose(0, 3, 1, 2).reshape(G, N, d_inner)
    out = _gated_out(y, z, layer, c)
    with jax.named_scope("ssm_scan"):
        state = state.astype(c.ssm_state_dtype)
    with jax.named_scope("ssm_conv"):
        conv_state = conv_state.astype(c.dtype)
    return out, (state, conv_state)


def decode(h: jax.Array, layer, c, ssm: jax.Array, conv: jax.Array,
           m: jax.Array, active: jax.Array):
    """One token a slot through Mamba layer ``m`` of the stacked states.

    h (B, 1, H); ssm (Lm, B, N, HD) and conv (Lm, K - 1, B, conv_dim) are
    the WHOLE stacks (the serving loops' carry): layer ``m`` is read and
    written in place.  A slot that is not ``active`` keeps both states as
    they are.  Returns (out (B, 1, H), ssm, conv)."""
    from ray_tpu.ops.ssm_state_update import ssm_state_update

    nh, hd, N, R = c.ssm_heads, c.ssm_head_dim, c.ssm_state, c.ssm_groups
    d_inner = nh * hd
    f32 = jnp.float32
    z, xbc_in, dt_raw = _project(h[:, 0], layer, c)
    with jax.named_scope("ssm_conv"):
        old = jax.lax.dynamic_index_in_dim(conv, m, 0, keepdims=False)
        window = jnp.concatenate([old, xbc_in[None].astype(conv.dtype)], 0)
        xbc = _conv_act(window, layer)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(active[None, :, None], window[1:], old), m, 0)
    with jax.named_scope("ssm_state_update"):
        x = xbc[:, :d_inner].reshape(-1, nh, hd)
        dt, A = _dt_a(dt_raw, layer, active[:, None])
        ssm, y = ssm_state_update(
            ssm, m, active,
            jnp.repeat(jnp.exp(dt * A), hd, axis=1),
            (dt[..., None] * x).reshape(-1, d_inner),
            *(xbc[:, at:at + R * N].reshape(-1, R, N)
              for at in (d_inner, d_inner + R * N)))
        y = y.reshape(-1, nh, hd) + layer["ssm_D"].astype(f32)[:, None] * x
    return _gated_out(y, z, layer, c)[:, None], ssm, conv

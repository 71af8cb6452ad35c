"""Mamba-1 mixer (selective state spaces, arXiv:2312.00752) for the
state-space layers of a decoder-hybrid-decoder (SambaY, arXiv:2507.06607:
``LlamaConfig`` kind ``mamba1``), beside ``mamba2.py``.

Per layer and token, with ``Di = ssm_inner`` channels, ``N = ssm_state``
state dimensions A CHANNEL, a ``dt`` of rank ``R = ssm_dt_rank`` and a
depthwise causal conv of ``K`` taps over the ``Di`` channels:

    [u | z] = W_in h                                       (Di | Di)
    u = silu(conv(u) + b)
    [delta | B | C] = W_x u                                (R | N | N)
    dt = softplus(W_dt delta + b_dt);   A = -exp(A_log)    (Di) ; (N, Di)
    S_t = exp(dt_t * A) * S_{t-1} + (dt_t * u_t) (x) B_t   (N, Di)
    y_t = S_t . C_t + D * u_t                              (Di)
    out = W_out (y_t * silu(z_t))

``A`` is a value per channel AND state dimension, so the decay is a whole
``(N, Di)`` matrix a token: Mamba-2's matmul form (one scalar decay a
head, ``mamba2.ssd_chunked``) and ``ops/ssm_state_update.py`` (a head's
block scaled by one number) do not compute it.  ``y_t``, after the skip
term and before the gate, is also the MEMORY a gated memory unit of a later
layer reads (``llama.layer_block``, kind ``gmu``): ``prefill`` and
``decode`` hand it back beside their result.

``prefill`` runs the recurrence over a prompt as it is written, one
position after another, in ONE kernel (``ops/mamba1_scan.py``: a block of
1,024 channels' state stays in vector registers from a row's first position
to its last and crosses HBM once a row, not once a position; no ``(P, N,
Di)`` tensor is made -- 4 GB in float32 at 12,288 positions); channels that
are not whole blocks (the toy presets) and a group of several rows keep
``selective_scan``, XLA's loop of ``ssm_chunk`` positions an iteration with
the state a loop carry.  A
padded position takes ``dt = 0`` and neither decays the state nor feeds it,
so a row's state is that of ITS OWN last real position, and its conv state
its last ``K - 1`` real pre-conv inputs (zeros before position 0); the
memory ``y`` PAST a row's last position is not defined (the kernel leaves
zeros from the next group of 8 positions on, the loop ``S . C_t``).
``decode`` advances every slot's state by one token in place in the stacked
states the serving loops carry; a row that is not ``active`` takes ``dt =
0`` and keeps its conv window.

The recurrence's arithmetic is float32 whatever type the state is stored
in (``ssm_state_dtype``), and so is everything between the projections:
the conv and its ``silu``, ``delta``, ``B``, ``C`` and ``dt`` (read out of
their projections in float32), the gate.  The projections take the
model's compute type with float32 accumulation.

Layouts for the TPU's (sublane, lane) tiles: the state is ``(Lm, B, N,
Di)`` and ``A_log`` ``(Lm, N, Di)``, channels on the lanes (the published
``(Di, N)`` would pad 16 state dimensions to 128 lanes); the conv state
``(Lm, K - 1, B, Di)`` and weight ``(Lm, K, Di)`` as ``mamba2``'s.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


def param_axes(c) -> Dict[str, tuple]:
    return {
        "ssm_in": ("layers", "embed", "mlp"),
        "ssm_conv_w": ("layers", None, "mlp"),
        "ssm_conv_b": ("layers", "mlp"),
        "ssm_x": ("layers", "mlp", None),
        "ssm_dt": ("layers", None, "mlp"),
        "ssm_dt_bias": ("layers", "mlp"),
        "ssm_A_log": ("layers", None, "mlp"),
        "ssm_D": ("layers", "mlp"),
        "ssm_out": ("layers", "mlp", "embed"),
    }


def init_params(key: jax.Array, c, layers: int, dtype: Any,
                dense) -> Dict[str, jax.Array]:
    """The Mamba convention for the leaves no matmul owns (as ``mamba2``
    draws them): ``A_log = log U[1, 16]``, ``dt_bias`` the inverse softplus
    of a ``dt`` drawn log-uniform in [1e-3, 1e-1], ``D = 1``, the conv
    fan-in scaled with a bias drawn as every bias is (``llama.BIAS_STD``);
    the four projections by ``dense``."""
    from ray_tpu.models.llama import BIAS_STD

    Di, N, R, K = c.ssm_inner, c.ssm_state, c.ssm_dt_rank, c.ssm_conv
    k_in, k_conv, k_x, k_dtw, k_dt, k_a, k_out, k_b = jax.random.split(key, 8)
    dt = jnp.exp(jax.random.uniform(
        k_dt, (layers, Di), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "ssm_in": dense(k_in, (layers, c.hidden_size, 2 * Di),
                        c.hidden_size),
        "ssm_conv_w": dense(k_conv, (layers, K, Di), K),
        "ssm_conv_b": (BIAS_STD * jax.random.normal(
            k_b, (layers, Di), jnp.float32)).astype(dtype),
        "ssm_x": dense(k_x, (layers, Di, R + 2 * N), Di),
        "ssm_dt": dense(k_dtw, (layers, R, Di), R),
        "ssm_dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "ssm_A_log": jnp.log(jax.random.uniform(
            k_a, (layers, N, Di), jnp.float32, 1.0, 16.0)).astype(dtype),
        "ssm_D": jnp.ones((layers, Di), dtype),
        "ssm_out": dense(k_out, (layers, Di, c.hidden_size), Di),
    }


def init_state(c, layers: int, batch: int) -> Dict[str, jax.Array]:
    """Zero recurrent and conv states of ``layers`` Mamba-1 layers for
    ``batch`` slots."""
    return {
        "ssm": jnp.zeros((layers, batch, c.ssm_state, c.ssm_inner),
                         c.ssm_state_dtype),
        "conv": jnp.zeros((layers, c.ssm_conv - 1, batch, c.ssm_inner),
                          c.dtype),
    }


@jax.named_scope("ssm_proj")
def _project_in(h: jax.Array, layer, c):
    """The conv's input u and the gate z."""
    from ray_tpu.models.llama import matmul

    uz = matmul(h, layer["ssm_in"].astype(c.dtype))
    return uz[..., :c.ssm_inner], uz[..., c.ssm_inner:]


@jax.named_scope("ssm_conv")
def _conv_act(window, layer):
    """``silu`` of the depthwise conv over ``window`` ((K, ..., Di), oldest
    tap first), float32."""
    w = layer["ssm_conv_w"].astype(jnp.float32)
    acc = layer["ssm_conv_b"].astype(jnp.float32)
    for k in range(w.shape[0]):
        acc = acc + window[k].astype(jnp.float32) * w[k]
    return jax.nn.silu(acc)


@jax.named_scope("ssm_proj")
def _dt_b_c(u: jax.Array, layer, c, live):
    """``(dt with 0 where not live, B, C)`` of the conv's output u
    (float32), all float32."""
    from ray_tpu.models.llama import matmul

    N, R = c.ssm_state, c.ssm_dt_rank
    dbc = matmul(u.astype(c.dtype), layer["ssm_x"].astype(c.dtype),
                 jnp.float32)
    dt = matmul(dbc[..., :R].astype(c.dtype), layer["ssm_dt"].astype(c.dtype),
                jnp.float32)
    dt = jax.nn.softplus(dt + layer["ssm_dt_bias"].astype(jnp.float32))
    return (jnp.where(live, dt, 0.0), dbc[..., R:R + N], dbc[..., R + N:])


def _advance(S, A, u_t, dt_t, b_t, c_t):
    """One position: S (G, N, Di), u_t, dt_t (G, Di), b_t, c_t (G, N), all
    float32 -> (S, y_t (G, Di) without the skip term)."""
    S = jnp.exp(dt_t[:, None, :] * A) * S \
        + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
    return S, jnp.sum(S * c_t[:, :, None], axis=1)


def selective_scan(u, dt, A, B, C, chunk: int):
    """The recurrence over P positions from a zero state, ``chunk``
    positions an iteration of the loop.  u, dt (G, P, Di) and B, C (G, P,
    N) float32, dt 0 at padded positions; A (N, Di) float32, negative.
    -> (y (G, P, Di) float32 without the ``D u`` term, the state after the
    last position (G, N, Di) float32)."""
    G, P, Di = u.shape

    def position(S, inputs):
        return _advance(S, A, *inputs)

    S, ys = jax.lax.scan(
        position, jnp.zeros((G, A.shape[0], Di), jnp.float32),
        tuple(jnp.swapaxes(a, 0, 1) for a in (u, dt, B, C)),
        unroll=max(1, min(chunk, P)))
    return jnp.swapaxes(ys, 0, 1), S


def _gated_out(y, z, layer, c):
    from ray_tpu.models.llama import matmul

    with jax.named_scope("ssm_out"):
        y = y * jax.nn.silu(z.astype(jnp.float32))
        return matmul(y.astype(c.dtype), layer["ssm_out"].astype(c.dtype))


def prefill(h: jax.Array, layer, c, lengths: Optional[jax.Array]):
    """The mixer over right-padded prompts from empty states.

    h (G, P, H) normed hidden states; lengths (G,) real lengths (None:
    every position is real).  Returns (out (G, P, H), (state (G, N, Di) in
    the state's storage type, conv state (K - 1, G, Di)), both as of each
    row's last real position, the memory y (G, P, Di) float32)."""
    from ray_tpu.ops.mamba1_scan import mamba1_scan

    G, P, _ = h.shape
    K = c.ssm_conv
    if lengths is None:
        lengths = jnp.full((G,), P, jnp.int32)
    u_in, z = _project_in(h, layer, c)
    with jax.named_scope("ssm_conv"):
        padded = jnp.pad(u_in, ((0, 0), (K - 1, 0), (0, 0)))
        u = _conv_act([padded[:, k:k + P] for k in range(K)], layer)
        # padded[i] is position i - (K - 1): the last K - 1 real inputs
        taps = lengths[:, None] \
            + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        conv_state = jnp.take_along_axis(
            padded, taps[:, :, None], axis=1).transpose(1, 0, 2)
        conv_state = conv_state.astype(c.dtype)
    live = (jnp.arange(P, dtype=jnp.int32)[None, :]
            < lengths[:, None])[..., None]
    dt, B, C = _dt_b_c(u, layer, c, live)
    with jax.named_scope("mamba1_scan"):
        A = -jnp.exp(layer["ssm_A_log"].astype(jnp.float32))
        y, state = mamba1_scan(u, dt, A, B, C,
                               layer["ssm_D"].astype(jnp.float32), lengths,
                               c.ssm_chunk)
        state = state.astype(c.ssm_state_dtype)
    return _gated_out(y, z, layer, c), (state, conv_state), y


def decode(h: jax.Array, layer, c, ssm: jax.Array, conv: jax.Array,
           m: jax.Array, active: jax.Array):
    """One token a slot through Mamba-1 layer ``m`` of the stacked states.

    h (B, 1, H); ssm (Lm, B, N, Di) and conv (Lm, K - 1, B, Di) are the
    WHOLE stacks (the serving loops' carry): layer ``m`` is read and
    written in place.  A slot that is not ``active`` keeps both states as
    they are.  Returns (out (B, 1, H), ssm, conv, the memory y (B, 1,
    Di) float32)."""
    f32 = jnp.float32
    u_in, z = _project_in(h[:, 0], layer, c)
    with jax.named_scope("ssm_conv"):
        old = jax.lax.dynamic_index_in_dim(conv, m, 0, keepdims=False)
        window = jnp.concatenate([old, u_in[None].astype(conv.dtype)], 0)
        u = _conv_act(window, layer)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(active[None, :, None], window[1:], old), m, 0)
    dt, B, C = _dt_b_c(u, layer, c, active[:, None])
    with jax.named_scope("mamba1_state_update"):
        A = -jnp.exp(layer["ssm_A_log"].astype(f32))
        S = jax.lax.dynamic_index_in_dim(ssm, m, 0, keepdims=False)
        # an inactive row's dt is 0: its state comes back as it was
        S, y = _advance(S.astype(f32), A, u, dt, B, C)
        ssm = jax.lax.dynamic_update_index_in_dim(
            ssm, S.astype(ssm.dtype), m, 0)
        y = y + layer["ssm_D"].astype(f32) * u
    return _gated_out(y, z, layer, c)[:, None], ssm, conv, y[:, None]

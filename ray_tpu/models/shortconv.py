"""Gated short convolution (LFM2's ``conv`` layers) for the layers of a
model that are not attention (``LlamaConfig.layer_types``).

Per layer and token, over the ``D = hidden_size`` channels, with a
depthwise causal conv of ``K = conv_taps`` taps and no bias, no
activation:

    [B | C | X] = W_in h                        (D | D | D, in that order)
    u   = B * X
    v_t = sum_{j < K} w_j * u_{t - (K - 1) + j}      zeros before position 0
    out = W_out (C * v)

The only state a slot keeps is ``u`` at its last ``K - 1`` positions: no
recurrent state.  ``prefill`` runs the conv over a right-padded prompt
(causal: a padded position reaches no real one) and hands back each row's
state as of ITS OWN last real position; ``decode`` advances every slot's
state by one token in place -- the states of all conv layers ``(Lc, K - 1,
B, D)`` are the carry of the serving loops (``llama_serve.decode_step``),
and a row that is not ``active`` keeps its window as it is.

``u`` is rounded to the compute type where it is made, in prefill and in
decode alike, because that is what the state stores: both forms convolve
the same values.  The conv's own arithmetic is float32; the projections
take the compute type with float32 accumulation, as every other matmul of
``models/llama.py``.  Layouts keep the channels minor: the state ``(Lc,
K - 1, B, D)`` and the weight ``(Lc, K, D)``, taps-major (the published
``(D, 1, K)`` would pad 3 taps to 128 lanes), oldest tap first.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp


# The state leaves of the serving cache this mixer keeps (no recurrent one).
HELD = ("conv",)


def param_axes(c) -> Dict[str, tuple]:
    return {
        "conv_in": ("layers", "embed", "mlp"),
        "conv_w": ("layers", None, "mlp"),
        "conv_out": ("layers", "mlp", "embed"),
    }


def init_params(key: jax.Array, c, layers: int, dense
                ) -> Dict[str, jax.Array]:
    """The projections and the taps, fan-in scaled by ``dense``
    (``llama.init_dense`` under the caller's dtype)."""
    k_in, k_conv, k_out = jax.random.split(key, 3)
    D = c.hidden_size
    return {
        "conv_in": dense(k_in, (layers, D, 3 * D), D),
        "conv_w": dense(k_conv, (layers, c.conv_taps, D), c.conv_taps),
        "conv_out": dense(k_out, (layers, D, D), D),
    }


def init_state(c, layers: int, batch: int) -> Dict[str, jax.Array]:
    """Zero conv states of ``layers`` conv layers for ``batch`` slots."""
    return {"conv": jnp.zeros((layers, c.conv_taps - 1, batch,
                               c.hidden_size), c.dtype)}


@jax.named_scope("conv_proj")
def _project(h: jax.Array, layer, c):
    """(u = B * X in the compute type, C)."""
    from ray_tpu.models.llama import matmul

    D = c.hidden_size
    bcx = matmul(h, layer["conv_in"].astype(c.dtype))
    b, gate, x = bcx[..., :D], bcx[..., D:2 * D], bcx[..., 2 * D:]
    u = (b.astype(jnp.float32) * x.astype(jnp.float32)).astype(c.dtype)
    return u, gate


def _taps(window, layer):
    """The depthwise conv over ``window`` (K of (..., D), oldest tap
    first), float32."""
    w = layer["conv_w"].astype(jnp.float32)
    acc = window[0].astype(jnp.float32) * w[0]
    for k in range(1, w.shape[0]):
        acc = acc + window[k].astype(jnp.float32) * w[k]
    return acc


@jax.named_scope("conv_out")
def _gated_out(v, gate, layer, c):
    """``W_out (C * v)``: v float32."""
    from ray_tpu.models.llama import matmul

    y = (gate.astype(jnp.float32) * v).astype(c.dtype)
    return matmul(y, layer["conv_out"].astype(c.dtype))


def prefill(h: jax.Array, layer, c, lengths: Optional[jax.Array]):
    """The mixer over right-padded prompts from empty states.

    h (G, P, D) normed hidden states; lengths (G,) real lengths (None:
    every position is real).  Returns (out (G, P, D), (conv state (K - 1,
    G, D),)), the state as of each row's last real position."""
    G, P, _ = h.shape
    K = c.conv_taps
    if lengths is None:
        lengths = jnp.full((G,), P, jnp.int32)
    u, gate = _project(h, layer, c)
    with jax.named_scope("short_conv"):
        padded = jnp.pad(u, ((0, 0), (K - 1, 0), (0, 0)))
        v = _taps([padded[:, k:k + P] for k in range(K)], layer)
        # padded[i] is position i - (K - 1): the last K - 1 real inputs
        taps = lengths[:, None] + jnp.arange(K - 1, dtype=jnp.int32)[None, :]
        state = jnp.take_along_axis(
            padded, taps[:, :, None], axis=1).transpose(1, 0, 2)
    return _gated_out(v, gate, layer, c), (state,)


def decode(h: jax.Array, layer, c, conv: jax.Array, m: jax.Array,
           active: jax.Array):
    """One token a slot through conv layer ``m`` of the stacked states.

    h (B, 1, D); conv (Lc, K - 1, B, D) is the WHOLE stack (the serving
    loops' carry): layer ``m`` is read and written in place.  A slot that
    is not ``active`` keeps its state as it is.  Returns (out (B, 1, D),
    conv)."""
    u, gate = _project(h[:, 0], layer, c)
    with jax.named_scope("short_conv"):
        old = jax.lax.dynamic_index_in_dim(conv, m, 0, keepdims=False)
        window = jnp.concatenate([old, u[None].astype(conv.dtype)], 0)
        v = _taps(window, layer)
        conv = jax.lax.dynamic_update_index_in_dim(
            conv, jnp.where(active[None, :, None], window[1:], old), m, 0)
    return _gated_out(v, gate, layer, c)[:, None], conv

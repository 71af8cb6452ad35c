"""Power-retention mixer: a SQUARED-PRODUCT linear attention whose state is
the symmetric square of the key (Manifest AI, *Scaling Context Requires
Rethinking Attention*, arXiv:2507.04239; the public ``retention`` package's
``power_retention`` at degree 2) for the layers of a model that keep a
matrix-valued state a key/value head and attend nothing (``LlamaConfig``
kind ``power``), beside ``kda.py`` / ``mamba2.py`` / ``mamba1.py`` /
``shortconv.py``.

Per layer and token, ``n`` a query head of ``n_heads``, ``m = n // R`` its
key/value head of ``n_kv_heads``, ``d = head_dim``, no biases:

    q_n = RoPE(rmsnorm_head(W_q h)_n)   k_m = RoPE(rmsnorm_head(W_k h)_m)
    v_m = (W_v h)_m                      gamma_m = log sigmoid((W_g h)_m)
    S_m <- e^gamma_m S_m + phi(k_m) v_m^T         (D, d)   D = d (d + 1) / 2
    z_m <- e^gamma_m z_m + phi(k_m)               (D,)
    o_n  = phi(q_n)^T S_m / (phi(q_n) . z_m + eps)
    out  = W_o concat_n o_n

``phi(u)`` the symmetric square of ``u``, so that ``phi(q) . phi(k) = (q .
k)^2``: the attention form of the same thing is ``a(t, i) = exp(sum_{i < s
<= t} gamma(s)) (q(t) . k(i))^2``, ``o(t) = sum_i a v_i / (sum_i a + eps)``
(any scale of the scores cancels).  The gate has no bias.  A config of
RANDOM weights may name ``power_gate_shift``: a constant a head added to the
gate's pre-activation (evenly spaced over the heads), which stands for what
a trained gate finds in the stream and a random one cannot
(``init_params``); no preset and no checkpoint's config names one.

The state is shared by a GROUP of ``R`` query heads (no other mixer here
has grouped readers), takes RoPE and q/k head norms on the way in (KDA and
Mamba take neither: ``ROPES``), and a layer holds one leaf a slot: ``ssm
(L, B, Hkv, d/2 + 2, d, d)`` float32, ``S`` and ``z`` as ``ops/
power_state_update.py`` lays them out (8,320 rows of ``phi`` at ``d = 128``
for the triangle's 8,256).

Two forms of the same recurrence.  ``prefill`` runs it ``power_chunk``
positions at a time (``ops/power_chunk.py``: quadratic inside a chunk, the
state across chunks), a block of at most 1,024 positions' projections,
norms and RoPE at once (``kda._block_len``); ``decode`` advances every slot's state by one token in place in
the stacked states the serving loops carry (``ops/power_state_update.py``).

Float32: the state, the gate, the scores and their squares, both sums and
everything between the projections and ``W_o``; the projections take the
model's compute type with float32 accumulation.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

# (a row goes through the chunked form in whole blocks of chunks, as a KDA
# layer's does: ``padded_len`` is what a launch is counted by)
from ray_tpu.models.kda import _block_len, padded_len  # noqa: F401
from ray_tpu.ops import power_state_update as _state

# ``state_mixer``'s callers hand this mixer the rope table of the positions
# they walk (``prefill(..., sin, cos)``, ``decode(..., sin, cos)``).
ROPES = True
# The state leaves of the serving cache this mixer keeps (no conv tail).
HELD = ("ssm",)
# The power of the product: the one that is built (``phi`` the symmetric
# SQUARE; ``serve.engine_build`` says it as ``power_degree``).
DEGREE = 2

def param_axes(c) -> Dict[str, tuple]:
    return {
        "power_q": ("layers", "embed", "heads"),
        "power_k": ("layers", "embed", "kv_heads"),
        "power_v": ("layers", "embed", "kv_heads"),
        "power_g": ("layers", "embed", None),
        "power_q_norm": ("layers", None),
        "power_k_norm": ("layers", None),
        "power_o": ("layers", "heads", "embed"),
    }


def init_params(key: jax.Array, c, layers: int, dtype: Any,
                dense) -> Dict[str, jax.Array]:
    """The projections by ``dense`` (truncated normal, fan-in scaled), the
    head norms at 1.  The gate's weights too: ``W_g h`` is then of deviation
    1 and of NO sign (the stream of random embeddings has no mean direction
    a linear map could find), so that half the positions decay the state by
    more than a half and a slot remembers a handful of tokens.  A config
    that serves these weights for their COST shifts the pre-activation by
    ``power_gate_shift``: a head's ``gamma`` is then about ``-e^(0.5 - b)``
    a position: from ``b = 6`` (a memory of ~250 positions) to ``b = 10``
    (~13,000: ``e^-0.6`` over 8,192 positions, far from 1 and from 0 in
    float32)."""
    D = c.hidden_size
    ks = jax.random.split(key, 5)
    return {
        "power_q": dense(ks[0], (layers, D, c.q_dim), D),
        "power_k": dense(ks[1], (layers, D, c.kv_dim), D),
        "power_v": dense(ks[2], (layers, D, c.kv_dim), D),
        "power_g": dense(ks[3], (layers, D, c.n_kv_heads), D),
        "power_q_norm": jnp.ones((layers, c.head_dim), dtype),
        "power_k_norm": jnp.ones((layers, c.head_dim), dtype),
        "power_o": dense(ks[4], (layers, c.q_dim, D), c.q_dim),
    }


def init_state(c, layers: int, batch: int) -> Dict[str, jax.Array]:
    """Zero states of ``layers`` power-retention layers for ``batch``
    slots."""
    return {"ssm": _state.init_state(layers, batch, c.n_kv_heads,
                                     c.head_dim).astype(c.ssm_state_dtype)}


def state_rows(c) -> int:
    """Rows of ``phi`` a key/value head's state has as laid out."""
    return _state.state_rows(c.head_dim)


@jax.named_scope("ssm_proj")
def _project(h: jax.Array, layer, c):
    """``(W_q h, W_k h, W_v h, W_g h)`` float32."""
    from ray_tpu.models.llama import matmul

    f32 = jnp.float32
    return tuple(matmul(h, layer[name].astype(c.dtype), f32)
                 for name in ("power_q", "power_k", "power_v", "power_g"))


def _head_norm(x, weight, eps):
    """RMSNorm over a head's ``d`` values, one weight of ``d`` a layer."""
    from ray_tpu.models.llama import rms_norm

    return rms_norm(x, weight, eps)


@jax.named_scope("power_gate")
def _heads(q, k, v, g, layer, c, sin, cos, live):
    """The recurrence's inputs of the projections (..., S, width) float32:
    ``(q (..., S, Hq, d), k, v (..., S, Hkv, d), gamma (..., S, Hkv))``, q
    and k normed a head and rotated, ``gamma`` the log-decay; ``k`` and
    ``gamma`` 0 where not ``live`` (..., S, 1)."""
    from ray_tpu.models.llama import apply_rope

    d = c.head_dim
    q = q.reshape(q.shape[:-1] + (c.n_heads, d))
    k = k.reshape(k.shape[:-1] + (c.n_kv_heads, d))
    v = v.reshape(v.shape[:-1] + (c.n_kv_heads, d))
    q = _head_norm(q, layer["power_q_norm"], c.norm_eps)
    k = _head_norm(k, layer["power_k_norm"], c.norm_eps)
    if c.rope:
        q, k = apply_rope(q, sin, cos), apply_rope(k, sin, cos)
    if c.power_gate_shift is not None:
        # (its two ends and evenly between, one a key/value head)
        g = g + jnp.asarray(np.linspace(
            *c.power_gate_shift, c.n_kv_heads).astype(np.float32))
    gamma = jax.nn.log_sigmoid(g)
    return (q, jnp.where(live[..., None], k, 0.0), v,
            jnp.where(live, gamma, 0.0))


@jax.named_scope("ssm_out")
def _project_out(o, layer, c):
    from ray_tpu.models.llama import matmul

    o = o.reshape(o.shape[:-2] + (-1,)).astype(c.dtype)
    return matmul(o, layer["power_o"].astype(c.dtype))


def prefill(h: jax.Array, layer, c, lengths: Optional[jax.Array], sin, cos):
    """The mixer over right-padded prompts from empty states.

    h (G, P, D) normed hidden states; lengths (G,) real lengths (None:
    every position is real); sin, cos the rope table of positions 0 .. P -
    1.  Returns (out (G, P, D), (state (G, Hkv, d/2 + 2, d, d),)), the
    state as of each row's last real position."""
    from ray_tpu.ops.power_chunk import power_chunk

    G, P, _ = h.shape
    if lengths is None:
        lengths = jnp.full((G,), P, jnp.int32)
    T = _block_len(P, c.power_chunk)
    nb = -(-P // T)
    pad = ((0, 0), (0, nb * T - P), (0, 0))
    h = jnp.pad(h, pad)
    sin, cos = (jnp.pad(jnp.broadcast_to(x, (G,) + x.shape[1:]), pad)
                for x in (sin, cos))

    def block(S, i):
        # (the projections too a block at a time: float32 rows of a whole
        # bucket would be 0.5 GB beside the states)
        at = i * T

        def cut(x):
            return jax.lax.dynamic_slice_in_dim(x, at, T, 1)

        live = ((at + jnp.arange(T, dtype=jnp.int32))[None, :]
                < lengths[:, None])[..., None]
        qh, kh, vh, gamma = _heads(*_project(cut(h), layer, c), layer, c,
                                   cut(sin), cut(cos), live)
        with jax.named_scope("power_chunk"):
            o, S = power_chunk(qh, kh, vh, gamma, S, c.power_chunk,
                               c.power_eps)
        return S, _project_out(o, layer, c)

    state, out = jax.lax.scan(
        block, init_state(c, 1, G)["ssm"][0].astype(jnp.float32),
        jnp.arange(nb, dtype=jnp.int32))
    out = jnp.moveaxis(out, 0, 1).reshape(G, nb * T, -1)[:, :P]
    with jax.named_scope("power_chunk"):
        state = state.astype(c.ssm_state_dtype)
    return out, (state,)


def decode(h: jax.Array, layer, c, ssm: jax.Array, m: jax.Array,
           active: jax.Array, sin, cos):
    """One token a slot through power-retention layer ``m`` of the stacked
    states.

    h (B, 1, D); ssm (L, B, Hkv, d/2 + 2, d, d) is the WHOLE stack (the
    serving loops' carry): layer ``m`` is read and written in place.  A
    slot that is not ``active`` keeps its state as it is.  sin, cos the
    rope table of each slot's position, (B, 1, d / 2).  Returns (out (B, 1,
    D), ssm)."""
    from ray_tpu.ops.power_state_update import power_state_update

    q, k, v, g = _project(h, layer, c)
    q, k, v, gamma = _heads(q, k, v, g, layer, c, sin, cos,
                            active[:, None, None])
    with jax.named_scope("power_state_update"):
        ssm, o = power_state_update(
            ssm, m, active, jnp.exp(gamma[:, 0]), q[:, 0], k[:, 0], v[:, 0],
            c.power_eps)
    return _project_out(o[:, None], layer, c), ssm

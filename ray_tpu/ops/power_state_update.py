"""One decode step of a power-retention layer's recurrence for TPU (Pallas):
a squared-product linear attention whose state is the symmetric square of
the key, every advancing slot's state read once and written once, where it
lies, and a slot that does not advance neither read nor written.

    S' = g S + phi(k) v^T;   z' = g z + phi(k)
    o_n = phi(q_n)^T S' / (phi(q_n) . z' + eps)        n a head of the group

per advancing slot and key/value head, ``g = exp(gamma)`` one scalar, the
``R = Hq / Hkv`` query heads of the group reading the ONE state.

**How ``phi`` is laid out** (``phi``, ``state_rows``): entry ``(s, a)`` is
``w_s u_a u_(a - s mod d)``, ``s = 0 .. d/2``, ``a = 0 .. d - 1``: every
unordered pair of channels once (``w = sqrt 2``), but the diagonal ``s = 0``
(``w = 1``) and ``s = d/2``, whose pairs come twice (``w = 1``), so that
``phi(q) . phi(k) = (q . k)^2`` exactly.  ``(d/2 + 1) d`` rows, 8,320 at
``d = 128`` for the 8,256 of the exact triangle (0.8% more): each ``s`` is
one whole row of lanes, built by ONE lane rotation (``pltpu.roll``, a row
``s`` of the tile rotated by ``s``) and never by a gather.

**The state** of all layers and slots is ONE array, ``(L, B, Hkv, d/2 + 2,
d, d)`` float32: the carry of the serving loops (``llama_serve.
decode_step``), this kernel's operand, aliased to its result.  Tile ``s <=
d/2`` is ``S[s]`` with the VALUES on the sublanes and the key channel ``a``
on the lanes, so that a row of ``phi`` is broadcast down the sublanes (one
replicated register) and ``v`` is a column made by one transpose a step;
the last tile holds the normaliser ``z``, ``(s, a)`` in its first ``d/2 +
1`` rows.  On ``ops/ssm_state_update.py``'s plan (``_plan``): the grid walks
the key/value heads and, inside, the slots; a slot's head is one block of
4.3 MB, fetched and written back by the pipeline at the layer a
scalar-prefetch operand names; a slot that is not active maps to the block
of the last active slot before it, so nothing moves for it.

All of it on the vector unit in float32 (the matrix unit would round the
state): ``phi(k)`` and the group's ``phi(q_n)`` are built in VMEM (six
tiles), the state streams through ``_STRIP`` value rows at a time under the
``d/2 + 1`` shifts, each register of it read once, updated, written and
multiplied into the group's five accumulators.

A state Mosaic cannot tile (``d`` not whole 128-lane tiles: the toy
presets) is updated by XLA, by shape (``_xla_update``: the same
arithmetic), on the chip and off it; interpret mode runs the kernel on the
CPU for the test suite, decided as ``ops/decode_attention.py`` decides.
"""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_flash = importlib.import_module("ray_tpu.ops.flash_attention")
_plan = importlib.import_module("ray_tpu.ops.ssm_state_update")._plan
LANES = _flash.LANES
# Value rows of a state tile the kernel holds in registers at a time: a
# register of state, one of ``v`` and the group's accumulators.
_STRIP = 8
_VMEM_LIMIT = 48 << 20
EPS = 1e-6


def shifts(d: int) -> int:
    """How many shifts ``s`` the layout has: ``d/2 + 1``."""
    return d // 2 + 1


def state_rows(d: int) -> int:
    """Rows of ``phi`` as laid out: ``(d/2 + 1) d``, 8,320 at 128 (the
    exact triangle has ``d (d + 1) / 2``, 8,256)."""
    return shifts(d) * d


def weights(d: int) -> np.ndarray:
    """``w_s``, (d/2 + 1,) float32."""
    w = np.full(shifts(d), math.sqrt(2.0), np.float32)
    w[0] = w[-1] = 1.0
    return w


def phi(u: jax.Array) -> jax.Array:
    """The symmetric square of ``u`` (..., d) as laid out: (..., d/2 + 1,
    d) float32, entry ``(s, a) = w_s u_a u_(a - s mod d)``."""
    d = u.shape[-1]
    u = u.astype(jnp.float32)
    partner = (np.arange(d)[None, :] - np.arange(shifts(d))[:, None]) % d
    return jnp.asarray(weights(d))[:, None] * u[..., None, :] \
        * jnp.take(u, jnp.asarray(partner), axis=-1)


def power(scores: jax.Array) -> jax.Array:
    """What the attention form raises a score to: ``phi(q) . phi(k)`` of
    the score ``q . k`` (the chunked form's quadratic part)."""
    return scores * scores


def normalised(num: jax.Array, den: jax.Array, eps: float) -> jax.Array:
    """``num / (den + eps)``: num (..., d), den (...)."""
    return num / (den + eps)[..., None]


def init_state(layers: int, slots: int, kv_heads: int, d: int):
    """Zero states: ``(layers, slots, Hkv, d/2 + 2, d, d)`` float32."""
    return jnp.zeros((layers, slots, kv_heads, shifts(d) + 1, d, d),
                     jnp.float32)


def split_state(state):
    """``(S (..., d/2 + 1, d [values], d [a]), z (..., d/2 + 1, d))`` of a
    stored state (..., d/2 + 2, d, d)."""
    n = state.shape[-3] - 1
    return state[..., :n, :, :], state[..., n, :n, :]


def join_state(S, z):
    """``split_state``'s inverse."""
    d = S.shape[-1]
    tile = jnp.pad(z, [(0, 0)] * (z.ndim - 2)
                   + [(0, d - z.shape[-2]), (0, 0)])
    return jnp.concatenate([S, tile[..., None, :, :]], axis=-3)


def read_state(S, z, q, eps: float = EPS):
    """``o_n = phi(q_n)^T S / (phi(q_n) . z + eps)``: S (..., Hkv, n, d, d),
    z (..., Hkv, n, d), q (..., Hq, d) -> (..., Hq, d) float32."""
    hkv = S.shape[-4]
    pq = phi(q)
    pq = pq.reshape(pq.shape[:-3] + (hkv, -1) + pq.shape[-2:])
    num = jnp.einsum("...mrsa,...msva->...mrv", pq, S,
                     precision=jax.lax.Precision.HIGHEST)
    den = jnp.einsum("...mrsa,...msa->...mr", pq, z,
                     precision=jax.lax.Precision.HIGHEST)
    return normalised(num, den, eps).reshape(q.shape)


def _xla_update(state, layer, active, decay, q, k, v, eps: float = EPS):
    held = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    S, z = split_state(held.astype(jnp.float32))
    pk = phi(k)                                         # (B, Hkv, n, d)
    g = decay.astype(jnp.float32)[..., None, None]
    S = g[..., None] * S + pk[..., None, :] * v.astype(
        jnp.float32)[..., None, :, None]
    z = g * z + pk
    new = jnp.where(active[:, None, None, None, None],
                    join_state(S, z).astype(state.dtype), held)
    o = read_state(S, z, q, eps)
    return (jax.lax.dynamic_update_index_in_dim(state, new, layer, 0),
            jnp.where(active[:, None, None], o, 0.0))


def _kernel(layer_ref, block_ref, mode_ref, s_ref, x_ref, o_ref, num_ref,
            den_ref, p_ref, *, d, readers):
    mode = mode_ref[pl.program_id(1)]
    n = shifts(d)
    f32 = jnp.float32

    @pl.when(mode == 1)
    def _update():
        x = x_ref[0, 0]                                  # (8, d)
        row = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0)
        w = jnp.where((row == 0) | (row == n - 1), 1.0,
                      jnp.where(row < n, math.sqrt(2.0), 0.0)).astype(f32)
        # phi of the group's queries and of the key, (s, a): a vector down
        # the sublanes, row s rotated by s
        for r in range(readers + 1):
            u = jnp.broadcast_to(x[r:r + 1], (d, d))
            p_ref[r] = w * u * pltpu.roll(u, 0, 1, stride=1, stride_axis=0)
        g = x[readers + 2:readers + 3]                   # (1, d), one value
        # v down the sublanes, the same along the lanes
        v_col = jnp.broadcast_to(x[readers + 1:readers + 2], (d, d)).T
        z = g * s_ref[0, 0, 0, n] + p_ref[readers]
        o_ref[0, 0, 0, n] = z
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, d), 1)
        sub = jax.lax.broadcasted_iota(jnp.int32, (8, d), 0)
        dens = jnp.zeros((8, d), f32)
        for r in range(readers):
            den = jnp.sum(p_ref[r] * z, axis=0, keepdims=True)
            den = jnp.sum(jnp.broadcast_to(den, (8, d)), axis=1,
                          keepdims=True)
            dens = jnp.where(sub == r, den, dens)
        den_ref[0, 0] = dens
        g_row = jnp.broadcast_to(g, (_STRIP, d))
        for j in range(d // _STRIP):
            at = pl.ds(j * _STRIP, _STRIP)
            v_strip = v_col[j * _STRIP:(j + 1) * _STRIP]

            def shift(s, accs):
                new = g_row * s_ref[0, 0, 0, s, at, :] \
                    + v_strip * p_ref[readers, pl.ds(s, 1), :]
                o_ref[0, 0, 0, s, at, :] = new
                return tuple(
                    acc + new * p_ref[r, pl.ds(s, 1), :]
                    for r, acc in enumerate(accs))

            accs = jax.lax.fori_loop(
                0, n, shift,
                tuple(jnp.zeros((_STRIP, d), f32) for _ in range(readers)))
            out = jnp.zeros((_STRIP, d), f32)
            for r, acc in enumerate(accs):
                out = jnp.where(lane[:_STRIP] == r,
                                jnp.sum(acc, axis=1, keepdims=True), out)
            num_ref[0, 0, at, :] = out

    @pl.when(mode == 2)
    def _through():
        o_ref[...] = s_ref[...]

    @pl.when(mode != 1)
    def _no_output():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.ones_like(den_ref)


def power_state_update(state: jax.Array, layer: jax.Array,
                       active: jax.Array, decay: jax.Array, q: jax.Array,
                       k: jax.Array, v: jax.Array, eps: float = EPS):
    """state (L, B, Hkv, d/2 + 2, d, d) float32, the stacked states; layer
    () int32; active (B,) bool; decay (B, Hkv) float32 = ``exp(gamma)``; q
    (B, Hq, d), k and v (B, Hkv, d) float32, q and k normed and rotated as
    the layer does.  Returns (state with layer ``layer`` of the active
    slots advanced, o (B, Hq, d) float32, the new state read by each query
    head of a key/value head's group; 0 for a slot that is not active)."""
    _l, slots, hkv, _n, d, _ = state.shape
    hq = q.shape[1]
    readers = hq // hkv
    if d % LANES or readers + 3 > 8 or state.dtype != jnp.float32:
        return _xla_update(state, layer, active, decay, q, k, v, eps)
    f32 = jnp.float32
    block, mode = _plan(active)
    # a head's vectors as eight rows of lanes: the group's queries, k, v,
    # the decay
    x = jnp.concatenate([
        q.astype(f32).reshape(slots, hkv, readers, d),
        k.astype(f32)[:, :, None], v.astype(f32)[:, :, None],
        jnp.broadcast_to(decay.astype(f32)[:, :, None, None],
                         (slots, hkv, 1, d)),
        jnp.zeros((slots, hkv, 8 - readers - 3, d), f32)], axis=2)

    def state_at(m, r, layer, block, mode):
        return (layer[0], block[r], m, 0, 0, 0)

    def vectors_at(m, r, layer, block, mode):
        return (block[r], m, 0, 0)

    def result_at(m, r, *_):
        return (r, m, 0, 0)

    tiles = pl.BlockSpec((1, 1, 1) + state.shape[3:], state_at)
    out, num, den = pl.pallas_call(
        functools.partial(_kernel, d=d, readers=readers),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(hkv, slots),
            in_specs=[tiles, pl.BlockSpec((1, 1, 8, d), vectors_at)],
            out_specs=[tiles, pl.BlockSpec((1, 1, d, d), result_at),
                       pl.BlockSpec((1, 1, 8, d), result_at)],
            scratch_shapes=[pltpu.VMEM((readers + 1, d, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((slots, hkv, d, d), f32),
                   jax.ShapeDtypeStruct((slots, hkv, 8, d), f32)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_flash._use_interpret(), name="power_state_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), block, mode, state, x)
    # num (B, Hkv, d [values], lanes: the group's heads first)
    o = normalised(jnp.swapaxes(num[..., :readers], -1, -2),
                   den[:, :, :readers, 0], eps)
    return out, o.reshape(slots, hq, d)

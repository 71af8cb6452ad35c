"""The chunked form of a power-retention layer's recurrence over whole
prompts: exact, ``chunk`` positions at a time.

Inside a chunk the quadratic (attention) form, a decay only as ``exp`` of a
difference that is <= 0; across chunks the state (``ops/power_state_update``
has its layout: ``phi``, ``split_state``).  With ``G_t`` the log-decay
summed from the chunk's start to ``t`` (falling), per key/value head ``m``
and query head ``n`` of its group:

    A_ti  = e^(G_t - G_i) (q_n(t) . k_m(i))^2              i <= t
    num_t = sum_i A_ti v_i + (e^G_t phi(q_n(t)))^T S       S the state the
    den_t = sum_i A_ti     + (e^G_t phi(q_n(t))) . z       chunk starts from
    o_t   = num_t / (den_t + eps)
    S <- e^G_C S + sum_i e^(G_C - G_i) phi(k_i) v_i^T;  z likewise

A padded position comes with ``gamma = 0`` and ``k = 0``: it neither decays
the state nor writes it, so a row's state is that of ITS OWN last real
position.

Per position and layer the state read is ``2 R Hkv D d`` FLOPs (85 M at the
published widths, D = 8,256), the update ``2 Hkv D d`` (17 M), whatever the
chunk; the quadratic form ``4 Hq d`` a PAIR, so ``chunk`` trades the pairs
(linear in it) against how often the state is read from and written to HBM
and how large a matmul's rows are (PERF.md section 6, PR 65: the sweep).

At a state Mosaic tiles (``d`` whole 128-lane tiles: the published widths)
a row's chunks are ONE kernel (``_kernel``): the grid walks the rows, the
key/value heads and, inside, the chunks; a head's state (``S`` and ``z``,
4.3 MB) stays in VMEM from chunk to chunk, and ``phi`` of a chunk's queries
and keys is built there a shift at a time -- a lane rotation and a product,
``_GROUP`` shifts side by side as ONE matmul's contraction -- so that
nothing of ``phi``'s size and no state between chunks goes through HBM.  A
group's ``R`` query heads are stacked down the rows of every matmul that
reads the state.  ``_xla_chunks`` is XLA's form of the same (``phi`` of ONE
chunk made a scan step, never of a bucket), which a toy preset's shape keeps
and the tests hold the kernel against.  Everything float32, every matmul at
``Precision.HIGHEST``.
"""

from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import power_state_update as _state
from ray_tpu.ops.power_state_update import EPS, join_state, split_state

_flash = importlib.import_module("ray_tpu.ops.flash_attention")
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
# Shifts whose products lie side by side as one matmul's contraction (the
# ``d/2 + 1`` shifts are whole groups: 65 = 13 x 5).
_GROUP = 5
_VMEM_LIMIT = 96 << 20


def engages(d: int, chunk: int) -> bool:
    """Whether the kernel takes a head of ``d`` in chunks of ``chunk``;
    ``_xla_chunks`` otherwise."""
    return d % _flash.LANES == 0 and chunk % 8 == 0 \
        and _state.shifts(d) % _GROUP == 0


def _xla_chunks(q, k, v, gamma, state, chunk: int, eps: float):
    N, T, Hq, d = q.shape
    Hkv = k.shape[2]
    R, C, nc = Hq // Hkv, chunk, T // chunk
    f32 = jnp.float32

    def chunks(x, heads):  # (N, T, H, ...) -> (nc, N, Hkv, ..., C, ...)
        x = x.astype(f32).reshape((N, nc, C) + heads + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 2, 2 + len(heads)), 1, 0)

    q = chunks(q, (Hkv, R))                             # (nc, N, Hkv, R, C, d)
    k, v = chunks(k, (Hkv,)), chunks(v, (Hkv,))         # (nc, N, Hkv, C, d)
    G = jnp.cumsum(chunks(gamma[..., None], (Hkv,))[..., 0], axis=-1)
    rows = jnp.arange(C, dtype=jnp.int32)
    causal = rows[:, None] >= rows[None, :]

    def one(carry, xs):
        S, z = carry
        q, k, v, G = xs
        scores = jnp.einsum("nmrtd,nmid->nmrti", q, k, precision=_HIGHEST)
        A = jnp.exp(jnp.where(causal, G[..., :, None] - G[..., None, :],
                              -jnp.inf))[:, :, None] * _state.power(scores)
        grown = jnp.exp(G)[:, :, None, :, None, None]
        pq = _state.phi(q) * grown             # (N, Hkv, R, C, n, d)
        num = jnp.einsum("nmrti,nmiv->nmrtv", A, v, precision=_HIGHEST) \
            + jnp.einsum("nmrtsa,nmsva->nmrtv", pq, S, precision=_HIGHEST)
        den = jnp.sum(A, -1) \
            + jnp.einsum("nmrtsa,nmsa->nmrt", pq, z, precision=_HIGHEST)
        last = jnp.exp(G[..., -1])
        pk = _state.phi(k) * jnp.exp(G[..., -1:] - G)[..., None, None]
        S = last[..., None, None, None] * S + jnp.einsum(
            "nmisa,nmiv->nmsva", pk, v, precision=_HIGHEST)
        z = last[..., None, None] * z + jnp.sum(pk, axis=2)
        return (S, z), _state.normalised(num, den, eps)

    (S, z), o = jax.lax.scan(one, split_state(state), (q, k, v, G))
    # (nc, N, Hkv, R, C, d) -> (N, T, Hq, d)
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 4, 2)
    return o.reshape(N, T, Hq, d), join_state(S, z)


def _dot_nt(a, b):
    """a (M, K), b (N, K) -> a b^T (M, N)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=_F32)


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=_F32)


def _kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, gl_ref, s_ref, o_ref,
            out_ref, *, d, readers, eps):
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        out_ref[...] = s_ref[...]

    n, C, R = _state.shifts(d), k_ref.shape[1], readers
    k, v = k_ref[0], v_ref[0]                              # (C, d)
    gcol, grow = gc_ref[0, 0], gr_ref[0, 0, 0]             # (C, 1), (1, C)
    last = jnp.exp(gl_ref[0, 0, 0])                        # (1, d), one value
    at = functools.partial(jax.lax.broadcasted_iota, jnp.int32, (C, C))
    decay = jnp.exp(jnp.where(at(0) >= at(1), gcol - grow,
                              -jnp.inf))                   # (C, C)
    grown = jnp.exp(gcol)                                  # (C, 1)
    # the group's heads down the rows: (R C, d)
    qs = jnp.concatenate(
        [q_ref[0, :, r * d:(r + 1) * d] for r in range(R)], 0)
    to_end = jnp.exp(gl_ref[0, 0, 0] - gcol)               # (C, d)
    # exp(G_C - G_i) v, turned: the update's left operand (d [values], C)
    vk_t = (v * to_end).T

    def group(j, carry):
        num, den = carry
        first = j * _GROUP
        pqs, pks, tiles = [], [], []
        for i in range(_GROUP):
            s = first + i
            w = jnp.where((s == 0) | (s == n - 1), 1.0, math.sqrt(2.0))
            pq = qs * pltpu.roll(qs, s, 1)
            pqs.append(pq)
            pks.append(k * pltpu.roll(k, s, 1))
            tiles.append(w * out_ref[0, 0, s])             # (d [values], d)
            z_s = w * out_ref[0, 0, n, pl.ds(s, 1), :]     # (1, d)
            den = den + pq * z_s
        # ONE matmul reads the group's tiles: its shifts are the contraction
        num = num + _dot_nt(jnp.concatenate(pqs, 1),
                            jnp.concatenate(tiles, 1))
        for i in range(_GROUP):
            s = first + i
            w = jnp.where((s == 0) | (s == n - 1), 1.0, math.sqrt(2.0))
            out_ref[0, 0, s] = last * out_ref[0, 0, s] \
                + w * _dot(vk_t, pks[i])
            out_ref[0, 0, n, pl.ds(s, 1), :] = \
                last * out_ref[0, 0, n, pl.ds(s, 1), :] \
                + w * jnp.sum(to_end * pks[i], axis=0, keepdims=True)
        return num, den

    num, den = jax.lax.fori_loop(
        0, n // _GROUP, group,
        (jnp.zeros((R * C, d), _F32), jnp.zeros((R * C, d), _F32)))
    for r in range(R):
        rows = slice(r * C, (r + 1) * C)
        scores = _dot_nt(qs[rows], k)                      # (C, C)
        a = decay * _state.power(scores)
        top = _dot(a, v) + grown * num[rows]
        low = jnp.sum(a, axis=1, keepdims=True) \
            + grown * jnp.sum(den[rows], axis=1, keepdims=True)
        o_ref[0, :, r * d:(r + 1) * d] = top / (low + eps)


@functools.partial(jax.jit, static_argnames=("chunk", "eps", "interpret"))
def _call(q, k, v, gamma, state, *, chunk, eps, interpret):
    """The kernel over q (N, T, Hq d), k and v (N, T, Hkv d), gamma (N, T,
    Hkv).  Jitted, so that the layers of a program share one lowering."""
    N, T, wide = k.shape
    hkv, d = state.shape[1], state.shape[-1]
    R, nc = q.shape[2] // wide, T // chunk
    # the log-decay summed from each chunk's start: a head's column, its
    # row, and the chunk's total along a row of lanes
    G = jnp.cumsum(jnp.moveaxis(gamma, 2, 1).reshape(N, hkv, nc, chunk), -1)
    total = jnp.broadcast_to(G[..., -1:, None], (N, hkv, nc, 1, d))

    def tile(heads):
        return pl.BlockSpec((1, chunk, heads * d), lambda n, m, c: (n, c, m))

    held = pl.BlockSpec((1, 1) + state.shape[2:],
                        lambda n, m, c: (n, m, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, d=d, readers=R, eps=eps),
        grid=(N, hkv, nc),
        in_specs=[tile(R), tile(1), tile(1),
                  pl.BlockSpec((1, 1, chunk, 1),
                               lambda n, m, c: (n, m, c, 0)),
                  pl.BlockSpec((1, 1, 1, 1, chunk),
                               lambda n, m, c: (n, m, c, 0, 0)),
                  pl.BlockSpec((1, 1, 1, 1, d),
                               lambda n, m, c: (n, m, c, 0, 0)),
                  held],
        out_specs=[tile(R), held],
        out_shape=[jax.ShapeDtypeStruct(q.shape, _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="power_chunk",
    )(q, k, v, G.reshape(N, hkv, T, 1), G[..., None, :], total, state)


def power_chunk(q: jax.Array, k: jax.Array, v: jax.Array, gamma: jax.Array,
                state: jax.Array, chunk: int, eps: float = EPS):
    """The recurrence over T positions (whole chunks) from ``state``.  q
    (N, T, Hq, d), k and v (N, T, Hkv, d), normed and rotated as the layer
    does; gamma (N, T, Hkv) the log-decay, 0 and ``k`` 0 at a padded
    position; state (N, Hkv, d/2 + 2, d, d) float32.  Returns (o (N, T, Hq,
    d) float32, the state after the last position): the kernel's where the
    shape ``engages``, ``_xla_chunks``' otherwise."""
    N, T, hq, d = q.shape
    if not engages(d, chunk):
        return _xla_chunks(q, k, v, gamma, state, chunk, eps)
    o, state = _call(
        q.reshape(N, T, -1).astype(_F32), k.reshape(N, T, -1).astype(_F32),
        v.reshape(N, T, -1).astype(_F32), gamma.astype(_F32),
        state.astype(_F32), chunk=chunk, eps=float(eps),
        interpret=_flash._use_interpret())
    return o.reshape(N, T, hq, d), state

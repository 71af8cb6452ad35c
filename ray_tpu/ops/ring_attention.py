"""Ring attention: sequence/context parallelism over the ICI ring.

The reference has no SP/CP at all (SURVEY.md §5.7 "Absent") — long
sequences are a first-class requirement here, so this implements
blockwise ring attention (Liu et al.-style): the sequence is sharded
over the mesh's ``seq`` axis; K/V chunks rotate around the ring via
``jax.lax.ppermute`` while each device computes flash-attention blocks
against its resident Q, merging partial results with a streaming
(log-sum-exp) accumulator.  The backward is a custom VJP that runs its
own ring: dK/dV accumulators travel with their K/V chunks and arrive
home after a full revolution.

Causality with contiguous sequence sharding: step 0 is the diagonal
(causal flash); step s>0 sees chunk (idx-s) mod n, fully visible iff
its index is below ours, else masked out (contributes nothing via
lse=-inf merging).  Above-diagonal steps still move data — the ring is
a fixed schedule — but their kernels are skipped at merge; a
zigzag/striped layout can reclaim that compute later.

Compute path: the Pallas flash kernels from
:mod:`ray_tpu.ops.flash_attention` (interpret mode on CPU).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import (NEG_INF, _bwd_impl, _fwd,
                              _use_interpret, flash_attention)

PPERM_AXIS_DOC = "seq"


def _merge(o_acc, lse_acc, o_c, lse_c):
    """Merge two normalized partial attention results.
    o: (B,H,S,D) f32; lse: f32 in the layout the flash kernels keep it
    in, a row's position the same in row-major order in each of them."""
    m = jnp.maximum(lse_acc, lse_c)
    a = jnp.exp(lse_acc - m)
    b = jnp.exp(lse_c - m)
    denom = a + b
    a, b, per_row = (x.reshape(*o_acc.shape[:3], 1) for x in (a, b, denom))
    o = (o_acc * a + o_c * b) / per_row
    return o, m + jnp.log(denom)


def _rotate(xs, axis_name, n):
    perm = [(i, (i + 1) % n) for i in range(n)]
    return [jax.lax.ppermute(x, axis_name, perm) for x in xs]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ring(q, k, v, axis_name, axis_size):
    out, _ = _ring_fwd_impl(q, k, v, axis_name, axis_size)
    return out


def _ring_fwd_impl(q, k, v, axis_name, axis_size):
    """Shard-local q/k/v: (B, S_loc, H, D).  Runs the forward ring."""
    B, S, Hq, D = q.shape
    scale = D ** -0.5
    qt = jnp.transpose(q, (0, 2, 1, 3)) * jnp.asarray(scale, q.dtype)
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    idx = jax.lax.axis_index(axis_name)
    interp = _use_interpret()

    o, lse = _fwd(qt, kt, vt, causal=True, block_q=None, block_k=None,
                  interpret=interp)
    o = o.astype(jnp.float32)
    k_rot, v_rot = kt, vt
    for step in range(1, axis_size):
        k_rot, v_rot = _rotate([k_rot, v_rot], axis_name, axis_size)
        src = (idx - step) % axis_size
        o_c, lse_c = _fwd(qt, k_rot, v_rot, causal=False, block_q=None,
                          block_k=None, interpret=interp)
        lse_c = jnp.where(src < idx, lse_c, NEG_INF)
        o, lse = _merge(o, lse, o_c.astype(jnp.float32), lse_c)
    o = o.astype(q.dtype)
    out = jnp.transpose(o, (0, 2, 1, 3))
    return out, (qt, kt, vt, o, lse)


def _ring_fwd(q, k, v, axis_name, axis_size):
    out, res = _ring_fwd_impl(q, k, v, axis_name, axis_size)
    return out, res


def _ring_bwd(axis_name, axis_size, res, g):
    qt, kt, vt, o, lse = res
    B, Hq, S, D = qt.shape
    Hkv = kt.shape[1]
    scale = D ** -0.5
    do = jnp.transpose(g, (0, 2, 1, 3))
    idx = jax.lax.axis_index(axis_name)
    interp = _use_interpret()

    dq = jnp.zeros((B, Hq, S, D), jnp.float32)
    k_rot, v_rot = kt, vt
    dk_rot = jnp.zeros((B, Hkv, S, D), jnp.float32)
    dv_rot = jnp.zeros((B, Hkv, S, D), jnp.float32)
    for step in range(axis_size):
        if step > 0:
            k_rot, v_rot, dk_rot, dv_rot = _rotate(
                [k_rot, v_rot, dk_rot, dv_rot], axis_name, axis_size)
        src = (idx - step) % axis_size
        # The kernels do the GQA (K/V taken at kv-head granularity, the
        # group's dk/dv added in their accumulator); float32 out, because
        # the chunks of the ring's steps are added here before the one cast.
        dq_c, dk_c, dv_c = _bwd_impl(
            qt, k_rot, v_rot, o.astype(qt.dtype), lse, do,
            causal=(step == 0), block_q=None, block_k=None,
            interpret=interp, out_dtype=jnp.float32)
        if step == 0:
            dq = dq + dq_c
            dk_rot = dk_rot + dk_c
            dv_rot = dv_rot + dv_c
        else:
            vis = src < idx
            dq = dq + jnp.where(vis, dq_c, 0.0)
            dk_rot = dk_rot + jnp.where(vis, dk_c, 0.0)
            dv_rot = dv_rot + jnp.where(vis, dv_c, 0.0)
    # One more hop brings every dK/dV accumulator back to its home
    # device (total rotations = axis_size).
    dk_rot, dv_rot = _rotate([dk_rot, dv_rot], axis_name, axis_size)

    dq = (dq * scale).astype(qt.dtype)
    dq = jnp.transpose(dq, (0, 2, 1, 3))
    dk = jnp.transpose(dk_rot.astype(kt.dtype), (0, 2, 1, 3))
    dv = jnp.transpose(dv_rot.astype(vt.dtype), (0, 2, 1, 3))
    return dq, dk, dv


_ring.defvjp(_ring_fwd, _ring_bwd)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   axis_name: str = "seq",
                   mesh=None) -> jax.Array:
    """Causal ring attention over the mesh's ``axis_name`` axis.

    q: (B, S, Hq, D); k/v: (B, S, Hkv, D), S = *global* sequence length
    (sharded over the seq axis by the surrounding pjit).  Falls back to
    single-device flash attention when there is no mesh or the seq axis
    is trivial.
    """
    from ray_tpu.parallel.sharding import current_mesh, current_rules

    mesh = mesh or current_mesh()
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        return flash_attention(q, k, v, causal=True)
    n = mesh.shape[axis_name]
    rules = current_rules()
    q_spec = rules.spec(("batch", "seq", "heads", "head_dim"))
    kv_spec = rules.spec(("batch", "seq", "kv_heads", "head_dim"))
    fn = jax.shard_map(
        functools.partial(_ring, axis_name=axis_name, axis_size=n),
        mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,
    )
    return fn(q, k, v)


def ring_attention_causal(q, k, v, positions=None):
    """Drop-in for models.llama.dot_attention (contiguous positions)."""
    from ray_tpu.ops.flash_attention import _check_default_positions

    _check_default_positions(positions, q.shape[1], "ring_attention_causal")
    return ring_attention(q, k, v)

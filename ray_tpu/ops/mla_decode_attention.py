"""Decode attention over a LATENT cache (DeepSeek-V2's MLA, absorbed
form), for TPU (Pallas): every head's query against the one row a token
keeps, each slot read only as far as it is long.

A latent cache holds, a token and layer, ``[c_kv ; k_rope]``: the
compressed K/V (``v_width`` values, normed) and the one roped key part
all heads share.  With the up-projections absorbed into the query and
the output (``models/llama.py`` ``mla_absorb``) a head's score against a
position is ONE dot product of its ``[q~ ; q_rope]`` with that row, and
its value is the row's first ``v_width`` values.  So the cache has no
head axis and this kernel differs from ``ops/decode_attention.py`` in
exactly that:

- the pool ``(L, B, S, W)`` stays in HBM (``memory_space=ANY``); blocks
  of ``block_k`` positions are copied in by the kernel itself, double
  buffered, the next block (of this slot or of the next slot that attends
  anything) in flight while this one is computed; blocks past a slot's
  last key are neither fetched nor computed;
- a block is fetched ONCE and used twice: whole as the keys (``W`` wide),
  its first ``v_width`` lanes as the values.  All ``H`` query heads of a
  slot are the rows of one matmul against it: no head mask, no bias;
- online softmax over a slot's blocks in float32, probabilities cast to
  the cache's type before ``P @ V`` with float32 accumulation; keys past
  a slot's length are masked by SELECTION, in the scores and in the
  values, so whatever lies there changes nothing.

A position costs ``W`` x 2 bytes and ``2 x H x (W + v_width)`` FLOPs: at
DeepSeek-V2's 128 heads, 576 and 512 that is 242 FLOP a byte, the v5e's
own ratio (197 TFLOP/s over 819 GB/s = 240).  Unlike every other decode
attention here this one is as much the MXU's as the memory's.

Interpret mode runs the same kernel on the CPU for the test suite (what
decides is ``flash_attention._use_interpret``, looked up at call time);
on a TPU a row Mosaic cannot read (not whole lanes: a toy width) is
attended by XLA over the layer's prefix (``_xla_decode_attention``).
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_flash = importlib.import_module("ray_tpu.ops.flash_attention")

LANES, NEG_INF = _flash.LANES, _flash.NEG_INF
# Positions a block: 256 rows of 576 bfloat16 values are 288 KiB, beside
# ``decode_attention``'s 256 KiB of K and as much of V.
BLOCK_K = 256
# Query heads are padded to whole bf16 sublane tiles.
_HEAD_TILE = 16


def block_k(s: int) -> int:
    """Positions per block: ``BLOCK_K``, at most the cache's length."""
    return min(BLOCK_K, s)


def _kernel(layer_ref, n_ref, q_ref, c_hbm, o_ref, cbuf, sems, m_scr, l_scr,
            acc_scr, *, bk, s_len, scale, v_width):
    slots = q_ref.shape[0]
    layer = layer_ref[0]

    def next_row(r):
        """The first slot at or after ``r`` that attends a key."""
        return jax.lax.while_loop(
            lambda r: (r < slots) & (n_ref[jnp.minimum(r, slots - 1)] == 0),
            lambda r: r + 1, r)

    def first_pos(j):
        # The last block of a cache whose length bk does not divide is
        # moved back inside it; the keys it shares with the block before
        # are masked below.
        return jnp.minimum(j * bk, s_len - bk)

    def copy(r, j, slot):
        rows = pl.ds(pl.multiple_of(first_pos(j), 8), bk)
        return pltpu.make_async_copy(c_hbm.at[layer, r, rows],
                                     cbuf.at[slot], sems.at[slot])

    o_ref[...] = jnp.zeros_like(o_ref)
    r0 = next_row(jnp.int32(0))

    @pl.when(r0 < slots)
    def _first():
        copy(r0, 0, 0).start()

    def block(state):
        r, j, slot = state
        n = n_ref[r]
        last = (j + 1) * bk >= n
        r_next = jax.lax.cond(last, lambda: next_row(r + 1), lambda: r)
        j_next = jnp.where(last, 0, j + 1)

        @pl.when(r_next < slots)
        def _prefetch():
            copy(r_next, j_next, 1 - slot).start()

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # Positions of this block that are keys of this slot and were not
        # in the block before: [lo, hi) of ``bk``.
        lo = j * bk - first_pos(j)
        hi = n - first_pos(j)
        copy(r, j, slot).wait()
        rows = cbuf[slot]
        s = jax.lax.dot_general(q_ref[r], rows, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((col >= lo) & (col < hi), s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        v = rows[:, :v_width]
        pos = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(pos < hi, v, jnp.zeros_like(v))
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        @pl.when(last)
        def _finalize():
            o_ref[r] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)

        return r_next, j_next, 1 - slot

    jax.lax.while_loop(lambda state: state[0] < slots, block,
                       (r0, jnp.int32(0), jnp.int32(0)))


def mla_decode_attention(q: jax.Array, pool: jax.Array, layer: jax.Array,
                         lens: jax.Array, active: jax.Array, *,
                         s_active: int, scale: float,
                         v_width: int) -> jax.Array:
    """Every head's absorbed query against layer ``layer`` of a latent
    pool.

    q: (B, H, W), a head's ``[q~ ; q_rope]``; pool: the WHOLE (L, B, S, W)
    cache, the row of position ``lens`` already written; lens: (B,) int32;
    active: (B,) bool.  Slot b attends rows ``[0, min(lens[b] + 1,
    s_active, S))`` if it is active and gives zeros if not.  -> (B, H,
    v_width) in the cache's dtype: per head the probability-weighted sum
    of the rows' first ``v_width`` values."""
    B, H, W = q.shape
    L, _, S, _ = pool.shape
    s_active = min(s_active, S)
    n = jnp.where(active, jnp.minimum(lens + 1, s_active), 0)
    interpret = _flash._use_interpret()
    if not interpret and (W % LANES or v_width % LANES or S % 8):
        return _xla_decode_attention(q, pool, layer, n, s_active, scale,
                                     v_width)

    bk = block_k(S)
    h_pad = -(-H // _HEAD_TILE) * _HEAD_TILE
    kernel = functools.partial(_kernel, bk=bk, s_len=S, scale=scale,
                               v_width=v_width)
    whole = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    attend = pl.pallas_call(
        kernel,
        name="mla_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                whole((B, h_pad, W), lambda i, *_: (0, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=whole((B, h_pad, v_width), lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((h_pad, LANES), jnp.float32),
                pltpu.VMEM((h_pad, LANES), jnp.float32),
                pltpu.VMEM((h_pad, v_width), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, h_pad, v_width), pool.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # every slot's queries and results stay in VMEM: 32 slots x
            # 128 heads x (576 + 512) bfloat16 are 8.9 MB
            vmem_limit_bytes=64 << 20),
        interpret=interpret,
    )
    q = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, h_pad - H), (0, 0)))
    with jax.named_scope("mla_decode_attention"):
        out = attend(jnp.asarray(layer, jnp.int32).reshape(1), n, q, pool)
    return out[:, :H]


def _xla_decode_attention(q, pool, layer, n, s_active, scale, v_width):
    """The same result by a masked einsum over the layer's first
    ``s_active`` rows (a copy of them: the kernel's reason to exist)."""
    rows = jax.lax.dynamic_slice(
        pool, (layer, 0, 0, 0),
        (1, pool.shape[1], s_active, pool.shape[3]))[0]
    s = jnp.einsum("bhw,bsw->bhs", q.astype(pool.dtype), rows,
                   preferred_element_type=jnp.float32) * scale
    keys = jnp.arange(s_active, dtype=jnp.int32)[None, None, :]
    s = jnp.where(keys < n[:, None, None], s, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(pool.dtype)
    out = jnp.einsum("bhs,bsv->bhv", p, rows[..., :v_width],
                     preferred_element_type=jnp.float32).astype(pool.dtype)
    return jnp.where((n > 0)[:, None, None], out, jnp.zeros_like(out))

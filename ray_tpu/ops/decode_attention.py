"""Decode attention for TPU (Pallas): one query a row against the K/V
cache where it lies, each row read only as far as it is long.

A decode step attends ONE new query per slot against that slot's cached
keys.  Written in XLA, the step first copies a layer's attended prefix
out of the stacked ``(L, B, S, Hkv, D)`` cache (a ``dynamic_slice`` has a
static size: the whole length bucket of every slot, live or not) and
then reads the copy again to attend it.  This kernel's operand is the
whole stack:

- K and V stay in HBM (``memory_space=ANY``); the layer index and the
  keys each row attends come as scalar-prefetch operands, and the kernel
  issues its own copies of ``block_k`` positions at a time, double
  buffered, the next block (of this row or of the next row that attends
  anything) in flight while this one is computed.  Blocks past a row's
  last key are neither fetched nor computed; a row that attends nothing
  costs a scalar comparison and gives zeros.
- The cache's rows are ``(position, kv head)`` pairs of ``D`` lanes
  (``(L, B, S * Hkv, D)``: the same bytes, no copy).  Slicing one head's
  rows out of a block would be a strided relayout of packed bf16; instead
  ALL query heads are multiplied against ALL of a block's rows in one
  matmul and the pairs whose kv head is not the query head's own are
  masked (``bias``).  The MXU's cost is the latching of K's tiles, which
  is the same either way, and the masked probabilities are exact zeros,
  so ``P @ V`` over the same rows is the grouped product.
- Online softmax over a row's blocks: scores and statistics in float32,
  probabilities cast to the cache's dtype before ``P @ V`` with float32
  accumulation (``llama._cache_attend``'s precisions, another order of
  summation).  Keys past a row's length are masked by SELECTION, in the
  scores and in V, so whatever lies there (stale rows, NaN) changes
  nothing.

GQA is ``group = Hq // Hkv`` query heads per kv head in ``bias``; MHA is
``group == 1``.  ``block_k`` follows from the bytes of a position: the
power of two NEAREST to ``_BLOCK_BYTES``, not the one below.  What a block
costs beside its bytes is the latency of its chain -- wait, ``q . K``,
max, exp, ``P @ V``: 0.7-0.9 us on a v5e, and twice the score tile adds
0.17 -- so a block whose DMA is shorter than that reads at the chain's
pace: 10 kv heads' 102 positions rounded DOWN to 64 (0.4 us of DMA) read
0.55 of the HBM peak, rounded to 128 they read 0.89.  A second score tile
that multiplies a kv head's rows against its own query heads alone (a
strided 32-bit view of the packed block) shortens the chain by 0.13 us
and, at the same 128 positions, reads 0.91: not worth its lines
(PERF.md section 6, PR 52; ``tools/decode_attention_sweep.py`` times the
kernel alone at every cell's shapes over several blocks).

A cache of kv heads that do not fill a sublane tile (4 of them) is handed
over already AS rows, ``(L, B, S * Hkv, D)``: stored with a dimension of
4 before the lanes it would be padded to a tile, occupy a multiple of its
bytes and not be contiguous.  The kernel is the same.  So it is for heads
of HALF a lane row (64), which a serving pool keeps two a 128-lane row
(``llama_serve.init_cache``; GQA 32/8 x 64 is then 32 query heads over 4
rows of 128): the queries come as wide as a row, zeros on the other head's
side (``llama_serve._attend_rows``), and this module sees rows of 128.  A
ring of the last ``S`` positions (a window layer's cache: position ``p``
in row ``p mod S``) is read through it unchanged: its rows carry their
RoPE from when they were written and softmax does not ask for their order,
so a row attends its first ``min(length, S)`` ring rows.

Interpret mode runs the same kernel on the CPU for the test suite; what
decides is ``flash_attention._use_interpret``, looked up at call time (a
test that compiles for a described chip steers that one function).
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

# The MODULE: ``ray_tpu.ops.flash_attention`` as an attribute is the
# function of that name.
_flash = importlib.import_module("ray_tpu.ops.flash_attention")

LANES, NEG_INF = _flash.LANES, _flash.NEG_INF
# One of K and V of one block: 128 positions of GQA 8 x 128 in bf16, 64
# of MHA 16 x 128.  A row's last block is read whole, so a larger block
# reads more past the row's end, and a block costs ~0.1 us beside its
# bytes at ~750 GB/s: on a v5e 256 KiB read the benchmark's rows fastest
# (PERF.md section 6, PR 29: 4.6 ms a step against 5.1 at 512 KiB and
# 6.4 at 1 MiB).  Four of them (K and V, double buffered) and a block's
# (Hq, block_k * Hkv) float32 scores stay far inside the scoped VMEM.
_BLOCK_BYTES = 256 << 10
# Query heads are padded to whole bf16 sublane tiles.
_HEAD_TILE = 16


def block_k(s: int, hkv: int, d: int, itemsize: int) -> int:
    """Positions per block: the power of two whose K is nearest to
    ``_BLOCK_BYTES`` (102 positions are 128, 85 are 64), at most the
    cache's length."""
    bk = max(8, _BLOCK_BYTES // (hkv * d * itemsize))
    low = 1 << (bk.bit_length() - 1)
    return min(2 * low if 2 * bk >= 3 * low else low, s)


def _tiles(hkv: int, d: int, as_rows: bool = False) -> bool:
    """Whether Mosaic can read the cache as ``(S * Hkv, D)`` rows: whole
    lanes, and kv heads that fill their sublane tile (XLA pads a
    second-minor dimension of 6 to 8, and the rows are then not
    contiguous) unless the cache is stored as rows already."""
    return d % LANES == 0 and (as_rows or hkv % 8 == 0)


def path_taken(hkv: int, d: int, as_rows: bool = False) -> str:
    """What attends a cache of ``hkv`` rows of ``d`` lanes a position on
    this backend: the Mosaic ``"kernel"`` (interpreted off the chip) or
    ``"xla"``.  Fixed with a config's shapes and the layout of its pool:
    ``serve.engine_build`` says it (docs/observability.md)."""
    return "kernel" if _flash._use_interpret() or _tiles(hkv, d, as_rows) \
        else "xla"


def _kernel(layer_ref, n_ref, q_ref, bias_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, sems, m_scr, l_scr, acc_scr,
            *, bk, hkv, s_len, scale, align, keep_ref=None):
    slots = q_ref.shape[0]
    width = bk * hkv
    layer = layer_ref[0]

    def next_row(r):
        """The first row at or after ``r`` that attends a key."""
        return jax.lax.while_loop(
            lambda r: (r < slots) & (n_ref[jnp.minimum(r, slots - 1)] == 0),
            lambda r: r + 1, r)

    def first_pos(j):
        # The last block of a cache whose length bk does not divide is
        # moved back inside it; the keys it shares with the block before
        # are masked below.
        return jnp.minimum(j * bk, s_len - bk)

    def copies(r, j, slot):
        rows = pl.ds(pl.multiple_of(first_pos(j) * hkv, align), width)
        return (pltpu.make_async_copy(k_hbm.at[layer, r, rows],
                                      kbuf.at[slot], sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[layer, r, rows],
                                      vbuf.at[slot], sems.at[1, slot]))

    def start(r, j, slot):
        for copy in copies(r, j, slot):
            copy.start()

    o_ref[...] = jnp.zeros_like(o_ref)
    r0 = next_row(jnp.int32(0))

    @pl.when(r0 < slots)
    def _first():
        start(r0, 0, 0)

    def block(state):
        r, j, slot = state
        n = n_ref[r]
        last = (j + 1) * bk >= n
        r_next = jax.lax.cond(last, lambda: next_row(r + 1), lambda: r)
        j_next = jnp.where(last, 0, j + 1)

        @pl.when(r_next < slots)
        def _prefetch():
            start(r_next, j_next, 1 - slot)

        @pl.when(j == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # Rows (position, kv head) of this block that are keys of this
        # row and were not in the block before: [lo, hi) of ``width``.
        lo = (j * bk - first_pos(j)) * hkv
        hi = (n - first_pos(j)) * hkv
        copy_k, copy_v = copies(r, j, slot)
        copy_k.wait()
        q = q_ref[r]
        s = jax.lax.dot_general(q, kbuf[slot], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale + bias_ref[...]
        if keep_ref is not None:
            # A selection that is data: NEG_INF at the rows of this block
            # the row does not attend.  A block of which it attends nothing
            # counts NEG_INF - NEG_INF = 0; a block that holds a key it
            # attends scales that away if it comes after (alpha = 0) and
            # adds exact zeros if it came before.  Every row attends a key.
            s = s + keep_ref[r, pl.ds(j, 1), :]
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((col >= lo) & (col < hi), s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        copy_v.wait()
        v = vbuf[slot]
        row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        v = jnp.where(row < hi, v, jnp.zeros_like(v))
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

        @pl.when(last)
        def _finalize():
            # A padded query head's row is masked everywhere: its sum
            # is the block's width, and it is cut off outside.
            o_ref[r] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)

        return r_next, j_next, 1 - slot

    jax.lax.while_loop(lambda state: state[0] < slots, block,
                       (r0, jnp.int32(0), jnp.int32(0)))


def _head_bias(hq_pad: int, hq: int, hkv: int, bk: int) -> np.ndarray:
    """(hq_pad, bk * hkv) float32: 0 where a block row's kv head is the
    query head's own, ``NEG_INF`` elsewhere."""
    own = np.arange(hq_pad) // (hq // hkv)
    own[hq:] = -1
    head = np.arange(bk * hkv) % hkv
    return np.where(own[:, None] == head[None, :], 0.0,
                    NEG_INF).astype(np.float32)


def decode_attention(q: jax.Array, ck: jax.Array, cv: jax.Array,
                     layer: jax.Array, lens: jax.Array, active: jax.Array,
                     *, s_active: int, scale: float,
                     hkv: int = 0, keep=None) -> jax.Array:
    """One query a row against layer ``layer`` of a stacked cache.

    q: (B, Hq, D); ck/cv: the WHOLE (L, B, S, Hkv, D) cache, or with
    ``hkv`` its rows (L, B, S * Hkv, D), the row of position ``lens``
    already written; lens: (B,) int32; active: (B,) bool.  Row b attends
    keys ``[0, min(lens[b] + 1, s_active, S))`` if it is active and gives
    zeros if not; with ``keep`` (B, s_active) bool, of those keys the ones
    it marks alone (a learned selection, ``models/indexer.py``: the rows are
    read as far as the row is long, and masked).  -> (B, Hq, D) in the
    cache's dtype.

    On a TPU a cache Mosaic cannot read as rows (kv heads by position
    that do not fill a sublane tile, a head that is not whole lanes and
    was not paired into rows that are) is attended by XLA, as
    ``llama._cache_attend`` over the layer's prefix."""
    B, hq, d = q.shape
    as_rows = ck.ndim == 4
    if as_rows:
        L, S = ck.shape[0], ck.shape[2] // hkv
    else:
        L, _, S, hkv, _ = ck.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    s_active = min(s_active, S)
    n = jnp.where(active, jnp.minimum(lens + 1, s_active), 0)
    if path_taken(hkv, d, as_rows) == "xla":
        if as_rows:
            ck, cv = (c.reshape(L, B, S, hkv, d) for c in (ck, cv))
        return _xla_decode_attention(q, ck, cv, layer, n, s_active, scale,
                                     keep)

    bk = block_k(S, hkv, d, ck.dtype.itemsize)
    hq_pad = -(-hq // _HEAD_TILE) * _HEAD_TILE
    rows = (L, B, S * hkv, d)
    # What a block's first row is a multiple of, for Mosaic to prove the
    # copy starts on a sublane tile: with 4 kv heads the heads alone do
    # not say so, the block and the cache's length do.
    align = hkv * math.gcd(bk, S) if as_rows else hkv
    kernel = functools.partial(_kernel, bk=bk, hkv=hkv, s_len=S,
                               scale=scale, align=align)
    whole = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    selection = ()
    if keep is not None:
        selection = (_keep_blocks(keep, s_active, S, bk, hkv),)
        unselected = kernel

        def kernel(layer_ref, n_ref, q_ref, bias_ref, keep_ref, *rest):
            unselected(layer_ref, n_ref, q_ref, bias_ref, *rest,
                       keep_ref=keep_ref)

    attend = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[
                whole((B, hq_pad, d), lambda i, *_: (0, 0, 0)),
                whole((hq_pad, bk * hkv), lambda i, *_: (0, 0)),
                *(whole(a.shape, lambda i, *_: (0, 0, 0))
                  for a in selection),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=whole((B, hq_pad, d), lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bk * hkv, d), ck.dtype),
                pltpu.VMEM((2, bk * hkv, d), cv.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((hq_pad, LANES), jnp.float32),
                pltpu.VMEM((hq_pad, LANES), jnp.float32),
                pltpu.VMEM((hq_pad, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, hq_pad, d), cv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_flash._use_interpret(),
    )
    q = jnp.pad(q.astype(ck.dtype), ((0, 0), (0, hq_pad - hq), (0, 0)))
    with jax.named_scope("decode_attention"):
        out = attend(jnp.asarray(layer, jnp.int32).reshape(1), n, q,
                     jnp.asarray(_head_bias(hq_pad, hq, hkv, bk)),
                     *selection, ck.reshape(rows), cv.reshape(rows))
    return out[:, :hq]


def _keep_blocks(keep, s_active: int, s_len: int, bk: int, hkv: int):
    """``keep`` (B, s_active) bool as what the kernel adds to a block's
    scores: float32 (B, blocks, bk * Hkv), 0 at a block's rows of a
    position the row attends and ``NEG_INF`` elsewhere, a block's positions
    those the kernel fetches for it (the last block of a length ``bk`` does
    not divide is moved back inside)."""
    blocks = -(-s_active // bk)
    first = np.minimum(np.arange(blocks) * bk, s_len - bk)
    pos = first[:, None] + np.arange(bk)[None, :]            # (blocks, bk)
    inside = jnp.asarray(pos < s_active)
    kept = keep[:, np.minimum(pos, s_active - 1)] & inside[None]
    return jnp.repeat(jnp.where(kept, 0.0, NEG_INF).astype(jnp.float32),
                      hkv, axis=2)


def _xla_decode_attention(q, ck, cv, layer, n, s_active, scale, keep=None):
    from ray_tpu.models.llama import _cache_attend

    def prefix(c):
        return jax.lax.dynamic_slice(
            c, (layer, 0, 0, 0, 0), (1,) + c.shape[1:2] + (s_active,)
            + c.shape[3:])[0]

    selection = () if keep is None else (
        jnp.broadcast_to(jnp.arange(s_active, dtype=jnp.int32), keep.shape),
        keep)
    out = _cache_attend(q[:, None], prefix(ck), prefix(cv),
                        (n - 1)[:, None], scale, *selection)[:, 0]
    return jnp.where((n > 0)[:, None, None], out, jnp.zeros_like(out))

"""Flash attention for TPU (Pallas), fwd + bwd, GQA-aware.

Memory-bound einsum attention materializes the (S, S) score matrix in
HBM (measured 6.5% MFU on the 125M bench at seq 2048); this kernel
streams K/V blocks through VMEM with an online softmax so scores never
leave the chip.  Design points:

- Layout (B, H, S, D) inside the kernel (S on sublanes, D on lanes);
  the public wrapper takes the model's (B, S, H, D) and transposes.
- GQA without materializing K/V per q-head, forward and backward: the
  kv BlockSpec index-maps ``head // group`` so grouped q-heads share the
  same K/V blocks (forward, dq).  dk/dv's grid is over KV heads and its
  one reduction axis walks the group's q heads, each q block of each, so
  the group is added in the float32 accumulator and K/V are fetched once
  a kv head.  dq, dk and dv leave the kernels in the type the caller
  asks for (``_bwd_impl``'s ``out_dtype``): rounded once, in
  ``_finalize``, with no float32 copy in HBM for XLA to sum and cast.
- Causal blocks strictly above the diagonal are skipped via
  ``pl.when`` + index-map redirect (no DMA, no compute).  Of a block the
  diagonal crosses, dq and dk/dv compute the causal strips only
  (``DIAG_STRIP``); the forward computes it whole and masks it.
- In serving a row is a prompt padded to its bucket, and the forward is
  told where it ends (``flash_prefill_attention``'s ``lengths``, a scalar
  prefetch): a q block that starts at or past its row's length is declined
  the same way -- no tile computed, no K/V block fetched for it -- and
  leaves as zeros.  The kernel sees whether its caller knows the lengths,
  nothing else: training's rows are full, its three kernels are built
  without the operand, and every row before a prompt's end sees the tiles
  it saw, in their order (``causal_computed_share``'s ``length``: what is
  left of a bucket's tiles).
- f32 accumulators in VMEM scratch; running (m, l) kept lane-replicated
  (shape (block_q, 128)) per TPU layout rules.
- lse is saved for the backward (recompute-based, à la FA-2).  It and
  the backward's ``delta`` cross HBM lane-dense, ``(B, H, 1, S)`` float32
  (4 KB a 1,024-row block; as ``(B, H, S, 1)`` the chip padded them
  128-fold).  The kernels turn them: the forward writes the row from its
  lane-replicated (m, l) once a q block at ``_finalize``, dq makes of it
  the column its score tile broadcasts against once a q block
  (``_store_stats`` / ``_load_stats``: 128 x 128 transposes), dk/dv
  computes its tile keys x queries and takes the row as it is.  A q
  block that is not whole lanes keeps the width-1 column
  (``_stats_dense``).

Interpret mode runs the same kernels on CPU for the simulated-mesh
test suite.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.parallel.sharding import shard_over_mesh

LANES = 128
# 1024 x 1024 tiles: forward, dq and dk/dv all compile through Mosaic
# at both train cells' shapes within the default scoped-VMEM limit
# (benchmarks/tests/test_aot_real_widths.py).  Read again by
# tools/flash_sweep.py (chip run, PR 31; PERF.md section 6): with
# 512 x 512 tiles the three kernels take x 1.37 of their time with
# 1024 x 1024 at both shapes (D = 64, S = 2,048: 8.41 against 6.12 ms;
# D = 128, S = 4,096: 3.55 against 2.60 ms; no strips in either), and
# x 1.5 with strips of 256 in both: four times the grid steps, each with
# its own waits for blocks.
DEFAULT_BLOCK = 1024
# Width of the causal strips in which dq and dk/dv walk a tile that the
# diagonal crosses (``_diag_strips``).  The same sweep: 256 read the
# shortest dq + dk/dv at both shapes (3.69 and 1.69 ms; 512: 3.79 and
# 1.72; none: 4.35 and 1.87); at 128 dk/dv takes longer than with no
# strips at all (2.78 against 2.37 ms at D = 64).
DIAG_STRIP = 256
NEG_INF = -1e30


def _use_interpret() -> bool:
    """Compiled through Mosaic iff the backend is ``tpu``; interpreted
    iff it is ``cpu`` (the simulated-mesh test suite).  Any other
    backend is an error: interpreting there would hide that the kernel
    never met the compiler."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"flash attention kernels run compiled on 'tpu' or interpreted "
        f"on 'cpu'; jax.default_backend() is {backend!r}")


def _block_sizes(sq: int, sk: int, block_q: Optional[int],
                 block_k: Optional[int]):
    bq = block_q or min(DEFAULT_BLOCK, sq)
    bk = block_k or min(DEFAULT_BLOCK, sk)
    while sq % bq:
        bq //= 2
    while sk % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


def _supported(sq: int, sk: int, d: int) -> bool:
    """Shapes the TPU kernel handles without padding."""
    if d > LANES and d % LANES:
        return False
    return sq % 8 == 0 and sk % LANES == 0


# ---------------------------------------------------------------------------
# The per-row softmax statistics (lse, delta) in HBM and in VMEM
# ---------------------------------------------------------------------------

# ``lse`` and ``delta`` are one float32 a query row.  A q block of whole
# lanes keeps them lane-dense, ``(B, H, 1, S)`` with position ``p`` at
# ``[0, p]``: a 1,024-row block is a 4 KB copy and the array is dense in
# HBM (``T(1,128)``; AOT, PR 41).  As a width-1 column ``(B, H, S, 1)``
# the tiled layout padded them 128-fold: 125.8 MB a layer each at 8 x 15
# x 2,048 for 0.98 MB of numbers, a 512 KB copy a block.  Chosen over
# ``(B, H, S / 128, 128)``, which read the same to 0.002 ms in all three
# kernels at both train cells' shapes (tools/flash_sweep.py, chip run, PR
# 41; PERF.md section 6), because its block ``(1, block_q)`` is legal for
# every ``block_q`` of whole lanes, where ``(block_q / 128, 128)`` has to
# be 8 rows or the whole sequence.  A block that is NOT whole lanes
# (``block_q % 128``: interpret-mode tests, a short or odd Sq) keeps the
# width-1 column.  ``block_q`` alone decides, here, never a caller.

def _stats_dense(block_q: int) -> bool:
    return block_q % LANES == 0


def _stats_shape(b: int, h: int, sq: int, block_q: int):
    return (b, h, 1, sq) if _stats_dense(block_q) else (b, h, sq, 1)


def _stats_spec(shape, block_q: int, q_map):
    """BlockSpec of a q block's statistics in an array of ``shape`` (a
    column, or rows of whole lanes a block); ``q_map`` is the index map
    of the block's ``(1, 1, block_q, D)`` rows."""
    if shape[-1] == 1:
        return pl.BlockSpec((1, 1, block_q, 1), q_map)

    def row_map(*grid):
        b, h, qi, _ = q_map(*grid)
        return (b, h, 0, qi)

    return pl.BlockSpec((1, 1, 1, block_q + -block_q % LANES), row_map)


def _stats_rows(x, block_q: int):
    """Statistics as lane-dense rows whatever the block: the lane-dense
    layout as it is; a width-1 column (XLA's copy, of a short or odd
    sequence) with each block's ``block_q`` values at the head of a row of
    whole lanes, ``(B, H, 1, blocks x lanes)``."""
    if _stats_dense(block_q):
        return x
    b, h = x.shape[:2]
    x = jnp.pad(x.reshape(b, h, -1, block_q),
                ((0, 0), (0, 0), (0, 0), (0, -block_q % LANES)))
    return x.reshape(b, h, 1, -1)


# A queries x keys score tile wants a row's statistic on every lane of
# the row; the lane-dense block has 128 rows' on the lanes of one sublane.
# The turn between them is a 128 x 128 transpose through the XLU of the
# 128 values replicated: exact, and once a q block.

def _store_stats(ref, rep):
    """Write a q block's statistics from ``rep`` (block_q, LANES),
    lane-replicated as ``m_scr`` / ``l_scr`` are."""
    if ref.shape[-1] == 1:
        ref[0, 0, :, :] = rep[:, :1]
        return
    for lo in range(0, rep.shape[0], LANES):
        ref[0, 0, :, lo:lo + LANES] = rep[lo:lo + LANES, :].T[:1, :]


def _load_stats(ref, scr):
    """Turn a q block's statistics into ``scr`` (block_q, LANES),
    lane-replicated: ``scr[rows, :1]`` is the column a score tile
    broadcasts against, sliced by rows as ``q_ref`` is."""
    if ref.shape[-1] == 1:
        scr[:] = jnp.broadcast_to(ref[0, 0, :, :], scr.shape)
        return
    for lo in range(0, scr.shape[0], LANES):
        scr[lo:lo + LANES, :] = jnp.broadcast_to(
            ref[0, 0, :, lo:lo + LANES], (LANES, LANES)).T


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _diag_strip(block_q: int, block_k: int) -> Optional[int]:
    """Strip width for a tile the diagonal crosses, or None where the
    tile is computed whole and masked by a select: tiles that are not
    square (the diagonal's place in them is not static) or that hold no
    two strips."""
    if block_q != block_k or block_q <= DIAG_STRIP or block_q % DIAG_STRIP:
        return None
    return DIAG_STRIP


def _diag_strips(block: int, strip: int, by: str):
    """The strips ``(rows, cols)`` of a square diagonal tile that hold
    what lies on or below the diagonal: ``by="cols"`` keys ``[c*g,
    (c+1)*g)`` meet the rows from ``c*g`` on, ``by="rows"`` rows ``[r*g,
    (r+1)*g)`` meet the keys before ``(r+1)*g``.  A strip's only elements
    above the diagonal are in its ``strip x strip`` corner ON it."""
    if by == "rows":
        return [((lo, lo + strip), (0, lo + strip))
                for lo in range(0, block, strip)]
    return [((lo, block), (lo, lo + strip)) for lo in range(0, block, strip)]


def q_blocks_run(seq: int, length: Optional[int] = None,
                 block_q: Optional[int] = None):
    """``(q blocks of a row of seq positions, those of them that run)``
    in the forward: all, or for a row whose real ``length`` the caller
    tells the kernel (``flash_prefill_attention``'s ``lengths``) the blocks
    that start before it -- the rest are declined."""
    bq, _ = _block_sizes(seq, seq, block_q, None)
    nq = seq // bq
    return nq, nq if length is None else min(nq, -(-length // bq))


def causal_computed_share(seq: int, block_q: Optional[int] = None,
                          block_k: Optional[int] = None,
                          strip: Optional[int] = None,
                          length: Optional[int] = None) -> float:
    """Share of the ``seq x seq`` score square that a causal kernel
    computes (a causal mask needs ``(1 + 1/seq) / 2`` of it): whole tiles
    below the diagonal, and of a tile the diagonal crosses its strips of
    width ``strip`` (default: what dq and dk/dv walk, ``_diag_strip``) or,
    with ``strip`` the tile's own width as in the forward, all of it.
    ``length``: the row is that long and the kernel knows (the serving
    forward): of its q blocks those that run (``q_blocks_run``)."""
    bq, bk = _block_sizes(seq, seq, block_q, block_k)
    if strip is None:
        strip = _diag_strip(bq, bk) or bq
    elif bq != bk or bq % strip:
        raise ValueError(f"no strips of {strip} in a {bq} x {bk} tile")
    computed = 0
    for q0 in range(0, bq * q_blocks_run(seq, length, bq)[1], bq):
        for k0 in range(0, seq, bk):
            if k0 > q0 + bq - 1:            # above the diagonal: skipped
                continue
            if k0 + bk - 1 > q0 and strip < bq:
                computed += sum((r1 - r0) * (c1 - c0) for (r0, r1), (c0, c1)
                                in _diag_strips(bq, strip, "cols"))
            else:
                computed += bq * bk
    return computed / (seq * seq)


def _causal_mask(s, row0, col0, query_axis=0):
    """Scores of keys after their query -> NEG_INF; ``row0`` / ``col0``
    place the block's first query and first key on one axis, queries along
    ``query_axis`` of ``s`` (1: the tile is keys x queries)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, query_axis)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                           1 - query_axis)
    return jnp.where(rows >= cols, s, NEG_INF)


def _band_mask(s, row0, col0, window, query_axis=0):
    """``_causal_mask`` and, beside it, keys ``window`` or more before
    their query -> NEG_INF."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, query_axis)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                           1 - query_axis)
    return jnp.where((rows >= cols) & (rows - cols < window), s, NEG_INF)


def _mask(s, mask, window=None, query_axis=0):
    """A tile's scores under ``_causal_dispatch``'s ``mask`` (None: the
    tile is clear of the diagonal and of the band's older edge)."""
    if mask is None:
        return s
    if window is None:
        return _causal_mask(s, *mask, query_axis=query_axis)
    return _band_mask(s, *mask, window, query_axis=query_axis)


# Which tiles a band of ``window`` keys leaves to compute, beside the
# causal rule.

def _in_band(qi, ki, window, block_q, block_k):
    """Tile (qi, ki) holds a key that some row of it still sees."""
    return ki * block_k + block_k - 1 > qi * block_q - window


def _band_first_k(qi, window, block_q, block_k):
    """The first k block that q block ``qi``'s band reaches back to."""
    return jnp.maximum((block_q * qi - window + 1) // block_k, 0)


def _band_last_q(ki, window, block_q, block_k):
    """The last q block that still sees k block ``ki``."""
    return (block_k * ki + block_k - 2 + window) // block_q


def _causal_dispatch(compute, causal, should_run, qi, ki,
                     block_q, block_k, strips=None, window=None):
    """Run ``compute(rows, cols, mask)`` under pl.when over what a tile
    has to compute.  ``rows`` / ``cols`` are static ``(start, stop)``
    within the tile; ``mask`` is None or the ``(row0, col0)`` that
    ``_mask`` takes.  Tiles below the diagonal and non-causal
    tiles are one unmasked call.  A tile the diagonal crosses is computed
    whole and masked, unless the kernel asks for ``strips`` (``"rows"`` or
    ``"cols"``) and the tile is square: then it is walked in
    ``_diag_strips``, nothing above them is computed, and a masked score
    adds an exact zero either way, so the sums are the same.  With
    ``window`` a tile the band's older edge crosses is masked whole as
    well (a strip's mask is the band's too: in the diagonal's own tile a
    strip's offsets are its distances)."""
    whole = (0, block_q), (0, block_k)
    if causal:
        on_diag = ki * block_k + block_k - 1 > qi * block_q
        crossed = on_diag
        if window is not None:
            # the tile's last row minus its first column: the farthest
            # any of its keys lies behind its query
            crossed |= qi * block_q + block_q - 1 - ki * block_k >= window

        @pl.when(should_run & jnp.logical_not(crossed))
        def _below():
            compute(*whole, None)

        strip = strips and _diag_strip(block_q, block_k)
        if strip and window is not None:
            # the band's older edge, away from the diagonal: whole, masked
            @pl.when(should_run & crossed & jnp.logical_not(on_diag))
            def _edge():
                compute(*whole, (qi * block_q, ki * block_k))

            crossed = on_diag

        @pl.when(should_run & crossed)
        def _diag():
            if not strip:
                compute(*whole, (qi * block_q, ki * block_k))
                return
            # Square, crossed and run: qi == ki, the diagonal is the tile's.
            for rows, cols in _diag_strips(block_q, strip, strips):
                compute(rows, cols, (rows[0], cols[0]))
    else:
        @pl.when(should_run)
        def _full():
            compute(*whole, None)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, block_q, block_k, nk, causal,
                window=None, keep_ref=None, lengths_ref=None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    if causal:
        # Run blocks on or below the diagonal only.
        should_run = ki * block_k <= qi * block_q + block_q - 1
        last_k = jnp.minimum(nk - 1,
                             (qi * block_q + block_q - 1) // block_k)
    else:
        should_run = True
        last_k = nk - 1
    if window is not None:
        # Nor tiles whose every key is ``window`` or more behind every
        # row.  A row that a crossed tile masks whole counts NEG_INF -
        # NEG_INF = 0 there; the tile of its own key, which always comes
        # after, scales that away (alpha = 0).
        should_run &= _in_band(qi, ki, window, block_q, block_k)
    if lengths_ref is not None:
        # Nor any tile of a q block that starts at or past its row's
        # length: padding, whose output nothing reads.  ``_init`` and
        # ``_finalize`` fire all the same (``last_k`` knows no length), so
        # the block leaves as zeros, ``lse`` NEG_INF: an all-masked row's.
        should_run &= qi * block_q < lengths_ref[pl.program_id(0)]

    def _compute(rows, cols, mask):
        r, c = slice(*rows), slice(*cols)
        q = q_ref[0, 0, r, :]
        k = k_ref[0, 0, c, :]
        v = v_ref[0, 0, c, :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = _mask(s, mask, window)
        if keep_ref is not None:
            # A mask that is data, below the diagonal too.  A row whose
            # keys of a tile are all masked counts NEG_INF - NEG_INF = 0
            # there; a tile that holds a key it sees scales that away
            # (alpha = 0) if it comes after, and adds exact zeros to it if
            # it came before.  Every row sees a key.
            s = jnp.where(keep_ref[0, r, c].astype(jnp.int32) != 0, s,
                          NEG_INF)
        m_prev = m_scr[r, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[r, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[r, :] = acc_scr[r, :] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[r, :] = jnp.broadcast_to(m_new, (m_new.shape[0], LANES))
        l_scr[r, :] = jnp.broadcast_to(l_new, (l_new.shape[0], LANES))

    # No strips here: a row's softmax bookkeeping, not its scores, is most
    # of this kernel's time, so strips by rows gain nothing and strips by
    # columns (one more online-softmax step each) lose (PERF.md section 6).
    _causal_dispatch(_compute, causal, should_run, qi, ki,
                     block_q, block_k, window=window)

    @pl.when(ki == last_k)
    def _finalize():
        l = l_scr[:, :1]
        # Fully-masked rows (possible in the non-causal ring steps)
        # produce l == 0; emit zeros and lse == NEG_INF so downstream
        # merging ignores them.
        l_safe = jnp.where(l > 0.0, l, 1.0)
        o_ref[0, 0, :, :] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        if lse_ref is not None:
            # on every lane of a row, as m and l are kept; the lane-dense
            # block takes the 128 x 128 turn of it (``_store_stats``)
            l_rep = l_scr[:]
            _store_stats(lse_ref, jnp.where(
                l_rep > 0.0, m_scr[:] + jnp.log(jnp.maximum(l_rep, 1e-37)),
                NEG_INF))


def _fwd(q, k, v, *, causal, block_q, block_k, interpret, window=None,
         name="flash_attention_fwd", with_lse=True, keep=None, lengths=None):
    """q: (B, Hq, Sq, D) pre-scaled; k: (B, Hkv, Sk, D); v: (B, Hkv, Sk,
    Dv), a head of its own width where the model's values have one
    (latent attention: 192-wide q/k, 128-wide v).
    Returns o (B, Hq, Sq, Dv), lse f32 (None without ``with_lse``: a
    forward nothing differentiates) as ``_stats_shape`` lays it out:
    (B, Hq, 1, Sq) where the q block is whole lanes, else (B, Hq, Sq, 1);
    ``lse.reshape(B, Hq, Sq)`` is a row's in either.  ``window`` (causal
    only): a query sees its last ``window`` keys, its own among them.
    ``keep`` (causal only): int8 (B, Sq, Sk), nonzero where a query sees a
    key, read a tile at a time beside K and V; every head shares it.
    ``lengths`` (causal only): int32 (B,), a scalar prefetch; a q block
    that starts at or past its row's length runs no tile and fetches
    nothing new, and leaves as zeros (``lse`` NEG_INF).  Without it the
    call is built as it always was: training's rows are full."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    Dv = v.shape[-1]
    group = Hq // Hkv
    bq, bk = _block_sizes(Sq, Sk, block_q, block_k)
    nq, nk = Sq // bq, Sk // bk
    grid = (B, Hq, nq, nk)

    # The index maps take the prefetched ``lengths`` last, where there are
    # any.  A declined q block (``_fwd_kernel``) fetches nothing new: q, K,
    # V and ``keep`` stay where the last step of the row's last q block
    # that runs left them.
    def running(b, qi, ki, lens):
        if not lens:
            return qi, ki
        last = jnp.maximum((lens[0][b] + bq - 1) // bq - 1, 0)
        return jnp.minimum(qi, last), jax.lax.select(qi > last, nk - 1, ki)

    def q_map(b, h, qi, ki, *lens):
        return (b, h, running(b, qi, ki, lens)[0], 0)

    def kv_map(b, h, qi, ki, *lens):
        qi, ki = running(b, qi, ki, lens)
        if causal and window is None:
            # Skipped above-diagonal blocks: redirect the prefetch to
            # block 0 (it will be needed for the next q row).
            ki = jax.lax.select(bk * ki <= bq * qi + bq - 1, ki, 0)
        elif causal:
            # Skipped blocks fetch nothing new: those behind the band
            # wait on its first block, those above the diagonal stay on
            # the diagonal's.
            ki = jnp.clip(ki, _band_first_k(qi, window, bq, bk),
                          (bq * qi + bq - 1) // bk)
        return (b, h // group, ki, 0)

    def o_map(b, h, qi, ki, *lens):
        return (b, h, qi, 0)

    def keep_map(b, h, qi, ki, *lens):
        return (b, running(b, qi, ki, lens)[0],
                kv_map(b, h, qi, ki, *lens)[2])

    kernel = functools.partial(_fwd_kernel, block_q=bq, block_k=bk,
                               nk=nk, causal=causal,
                               **({} if window is None
                                  else {"window": window}))
    lse_shape = _stats_shape(B, Hq, Sq, bq)
    out_specs = [pl.BlockSpec((1, 1, bq, Dv), o_map),
                 _stats_spec(lse_shape, bq, o_map)]
    out_shape = [jax.ShapeDtypeStruct((B, Hq, Sq, Dv), q.dtype),
                 jax.ShapeDtypeStruct(lse_shape, jnp.float32)]
    if not with_lse:
        with_stats = kernel

        def kernel(q_ref, k_ref, v_ref, o_ref, *scratch, **kw):
            with_stats(q_ref, k_ref, v_ref, o_ref, None, *scratch, **kw)

        out_specs, out_shape = out_specs[:1], out_shape[:1]
    in_specs = [
        pl.BlockSpec((1, 1, bq, D), q_map),
        pl.BlockSpec((1, 1, bk, D), kv_map),
        pl.BlockSpec((1, 1, bk, Dv), kv_map),
    ]
    operands = (q, k, v)
    if keep is not None:
        unmasked = kernel

        def kernel(q_ref, k_ref, v_ref, keep_ref, *rest, **kw):
            unmasked(q_ref, k_ref, v_ref, *rest, keep_ref=keep_ref, **kw)

        in_specs.append(pl.BlockSpec((1, bq, bk), keep_map))
        operands += (keep,)
    spec = dict(grid=grid, in_specs=in_specs, out_specs=out_specs,
                scratch_shapes=[
                    pltpu.VMEM((bq, LANES), jnp.float32),
                    pltpu.VMEM((bq, LANES), jnp.float32),
                    pltpu.VMEM((bq, Dv), jnp.float32),
                ])
    if lengths is not None:
        unbounded = kernel

        def kernel(lengths_ref, *rest):
            unbounded(*rest, lengths_ref=lengths_ref)

        spec = {"grid_spec": pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **spec)}
        operands = (lengths.astype(jnp.int32), *operands)
    fwd = pl.pallas_call(
        kernel,
        name=name,
        **spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    # The kernel's ``name=`` is what a device trace shows (XLA names the
    # custom call after the innermost scope of its name stack, and the
    # name is pushed as that); this scope lands in the HLO's ``op_name``
    # metadata only (PERF.md section 3).
    with jax.named_scope("flash_attention.fwd"):
        o, *lse = fwd(*operands)
    return o, (lse[0] if lse else None)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, lse_scr, delta_scr, *, block_q, block_k, nk, causal,
               window=None):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        _load_stats(lse_ref, lse_scr)
        _load_stats(delta_ref, delta_scr)

    if causal:
        should_run = ki * block_k <= qi * block_q + block_q - 1
        last_k = jnp.minimum(nk - 1,
                             (qi * block_q + block_q - 1) // block_k)
    else:
        should_run = True
        last_k = nk - 1
    if window is not None:
        # nor tiles wholly behind the band, as in the forward: the walk
        # still ends on the diagonal's tile, which always runs
        should_run &= _in_band(qi, ki, window, block_q, block_k)

    def _compute(rows, cols, mask):
        r, c = slice(*rows), slice(*cols)
        q = q_ref[0, 0, r, :]
        k = k_ref[0, 0, c, :]
        v = v_ref[0, 0, c, :]
        do = do_ref[0, 0, r, :]
        lse = lse_scr[r, :1]
        delta = delta_scr[r, :1]
        s = _mask(jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32),
                  mask, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[r, :] = dq_scr[r, :] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(_compute, causal, should_run, qi, ki,
                     block_q, block_k, strips="rows", window=window)

    @pl.when(ki == last_k)
    def _finalize():
        dq_ref[0, 0, :, :] = dq_scr[:].astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr,
                 *, block_q, block_k, nq, group, causal, window=None):
    ki = pl.program_id(2)
    # The reduction walks the kv head's ``group`` q heads, ``nq`` q blocks
    # of each (``_bwd_impl``'s ``q_map_kv`` picks the head): ``step`` is
    # q block ``step % nq`` of whichever head it is.  Head by head, not
    # block by block: 1.667 against 1.717 ms at 8 x 15 / 5 x 2,048 x 64,
    # level at 1 x 16 / 8 x 4,096 x 128 (tools/flash_sweep.py, chip run,
    # PR 42).
    step = pl.program_id(3)
    qi = jax.lax.rem(step, nq)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    if causal:
        # Need q rows at or below this kv block's diagonal.
        should_run = qi * block_q + block_q - 1 >= ki * block_k
    else:
        should_run = True
    if window is not None:
        # nor q blocks whose every row left this kv block behind
        should_run &= _in_band(qi, ki, window, block_q, block_k)

    def _compute(rows, cols, mask):
        # The tile keys x queries, ``K Q^T``: a query's statistic is then
        # wanted on the query's LANE, where the lane-dense row has it, so
        # this kernel turns nothing; and dv = P^T dO, dk = dS^T Q take
        # ``pt`` / ``dst`` as they are made, where P and dS had to go
        # through the XLU transposed (chip run, PR 41: 2.29 -> 1.84 ms at
        # 8 x 15 x 2,048 x 64).  Same products, same float32 sums.
        r, c = slice(*rows), slice(*cols)
        q = q_ref[0, 0, r, :]
        k = k_ref[0, 0, c, :]
        v = v_ref[0, 0, c, :]
        do = do_ref[0, 0, r, :]
        lse = lse_ref[0, 0, :, r]
        delta = delta_ref[0, 0, :, r]
        st = _mask(jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                       preferred_element_type=jnp.float32),
                   mask, window, query_axis=1)
        pt = jnp.exp(st - lse)
        dv_scr[c, :] = dv_scr[c, :] + jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        dst = (pt * (dpt - delta)).astype(q.dtype)
        dk_scr[c, :] = dk_scr[c, :] + jax.lax.dot_general(
            dst, q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    _causal_dispatch(_compute, causal, should_run, qi, ki,
                     block_q, block_k, strips="cols", window=window)

    @pl.when(step == group * nq - 1)
    def _finalize():
        dk_ref[0, 0, :, :] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_impl(q, k, v, o, lse, do, *, causal, block_q, block_k,
              interpret, out_dtype, window=None):
    """q, o, do (B, Hq, Sq, D); k, v (B, Hkv, Sk, D), ``Hq // Hkv`` q
    heads to a kv head (1: the same program, a one-head walk); lse as
    ``_fwd`` returns it for these blocks.  Returns dq (B, Hq, Sq, D) and
    dk, dv (B, Hkv, Sk, D), un-scaled, in ``out_dtype``: each a float32
    accumulator rounded once as it leaves VMEM.  ``delta`` is made in
    ``lse``'s layout: no (B, Hq, S, 1) value exists where the q block is
    whole lanes.  ``window`` (causal only): the forward's band; tiles
    wholly outside it are neither fetched nor computed by dq's walk over k
    blocks or dk/dv's over q blocks.  Without it both kernels are built as
    they always were."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, _ = k.shape
    group = Hq // Hkv
    bq, bk = _block_sizes(Sq, Sk, block_q, block_k)
    nq, nk = Sq // bq, Sk // bk

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).reshape(lse.shape)
    banded = {} if window is None else {"window": window}

    def q_map(b, h, qi, ki):
        return (b, h, qi, 0)

    def k_map_q(b, h, qi, ki):
        if causal and not banded:
            ki = jax.lax.select(bk * ki <= bq * qi + bq - 1, ki, 0)
        elif causal:
            # as the forward: blocks behind the band wait on its first,
            # those above the diagonal stay on the diagonal's
            ki = jnp.clip(ki, _band_first_k(qi, window, bq, bk),
                          (bq * qi + bq - 1) // bk)
        return (b, h // group, ki, 0)

    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, block_q=bq, block_k=bk, nk=nk,
                          causal=causal, **banded),
        name="flash_attention_dq",
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), q_map),
            pl.BlockSpec((1, 1, bk, D), k_map_q),
            pl.BlockSpec((1, 1, bk, D), k_map_q),
            pl.BlockSpec((1, 1, bq, D), q_map),
            _stats_spec(lse.shape, bq, q_map),
            _stats_spec(lse.shape, bq, q_map),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), q_map),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), out_dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32),
                        pltpu.VMEM((bq, LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope("flash_attention.dq"):
        dq = dq_call(q, k, v, do, lse, delta)

    def kv_map(b, h, ki, r):
        # the same block through the whole walk: fetched once a kv head
        return (b, h, ki, 0)

    def q_map_kv(b, h, ki, r):
        qi = r % nq
        if causal:
            # Above-diagonal (skipped) blocks: redirect the prefetch to
            # the same q head's first block that runs, the next one wanted.
            # (past the band: stay on its last block that runs)
            qi = jnp.clip(qi, bk * ki // bq,
                          jnp.minimum(_band_last_q(ki, window, bq, bk),
                                      nq - 1) if banded else nq - 1)
        return (b, h * group + r // nq, qi, 0)

    lse_rows, delta_rows = _stats_rows(lse, bq), _stats_rows(delta, bq)
    dkdv_call = pl.pallas_call(
        functools.partial(_dkdv_kernel, block_q=bq, block_k=bk, nq=nq,
                          group=group, causal=causal, **banded),
        name="flash_attention_dkdv",
        grid=(B, Hkv, nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), q_map_kv),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bq, D), q_map_kv),
            _stats_spec(lse_rows.shape, bq, q_map_kv),
            _stats_spec(lse_rows.shape, bq, q_map_kv),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, D), kv_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, Sk, D), out_dtype),
            jax.ShapeDtypeStruct((B, Hkv, Sk, D), out_dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )
    with jax.named_scope("flash_attention.dkdv"):
        dk, dv = dkdv_call(q, k, v, do, lse_rows, delta_rows)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API (custom VJP)
# ---------------------------------------------------------------------------

# The primal runs the pallas forward OUTSIDE the custom_vjp (under
# stop_gradient so AD never tries to transpose the kernel) and feeds
# (qt, kt, vt, o, lse) into ``_flash_core``, an identity-on-o
# custom_vjp whose backward runs the dq/dkdv kernels.  This makes
# every backward residual a NAMED value in the primal graph
# (checkpoint_name), so a remat policy can SAVE attention residuals —
# ``save_only_these_names(*FLASH_RESIDUAL_NAMES)`` skips re-running the
# attention forward in the backward pass entirely (llama remat_policy
# "attn"), for ~129 MB/layer at bench shapes.

FLASH_RESIDUAL_NAMES = ("flash_q", "flash_k", "flash_v", "flash_o",
                        "flash_lse")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash_core(qt, kt, vt, o, lse, causal, block_q, block_k, window):
    return o


def _flash_core_fwd(qt, kt, vt, o, lse, causal, block_q, block_k, window):
    return o, (qt, kt, vt, o, lse)


def _flash_core_bwd(causal, block_q, block_k, window, res, g):
    qt, kt, vt, o, lse = res
    # ``g`` is already (B, Hq, Sq, D).  dq is returned w.r.t. the
    # PRE-SCALED qt: the outer qt = q * scale chain applies the scale
    # factor during transposition (the old whole-function custom_vjp had
    # to undo it by hand).
    dq, dk, dv = _bwd_impl(qt, kt, vt, o, lse, g, causal=causal,
                           block_q=block_q, block_k=block_k,
                           interpret=_use_interpret(), out_dtype=qt.dtype,
                           window=window)
    # o and lse are functions of q/k/v computed under stop_gradient in
    # the primal; their cotangents are structurally zero.
    return dq, dk, dv, jnp.zeros_like(o), jnp.zeros_like(lse)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def _named_packed(x, name):
    """checkpoint_name with a lane-friendly storage layout: head_dim is
    usually 64/outputs (B,H,S,D) — the TPU (8,128) tile pads D<128 to
    128 lanes, DOUBLING the saved residual's HBM cost.  Regroup rows so
    the stored value's last dim is 128 (a contiguous row-major reshape);
    consumers recompute the cheap un-reshape from the saved value."""
    from jax.ad_checkpoint import checkpoint_name

    D = x.shape[-1]
    if D < LANES and LANES % D == 0 and x.shape[-2] % (LANES // D) == 0:
        g = LANES // D
        shp = (*x.shape[:-2], x.shape[-2] // g, LANES)
        return checkpoint_name(x.reshape(shp), name).reshape(x.shape)
    return checkpoint_name(x, name)


def _flash(q, k, v, causal, block_q, block_k, window=None):
    B, S, Hq, D = q.shape
    scale = D ** -0.5
    qt = jnp.transpose(q, (0, 2, 1, 3)) * jnp.asarray(scale, q.dtype)
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    o, lse = _fwd(jax.lax.stop_gradient(qt), jax.lax.stop_gradient(kt),
                  jax.lax.stop_gradient(vt), causal=causal,
                  block_q=block_q, block_k=block_k,
                  interpret=_use_interpret(), window=window)
    qt = _named_packed(qt, "flash_q")
    kt = _named_packed(kt, "flash_k")
    vt = _named_packed(vt, "flash_v")
    o = _named_packed(o, "flash_o")
    # lane-dense, lse has nothing to pack: the residual the "attn" remat
    # policy saves IS the kernels' operand (a width-1 column still packs)
    lse = _named_packed(lse, "flash_lse")
    out = _flash_core(qt, kt, vt, o, lse, causal, block_q, block_k, window)
    return jnp.transpose(out, (0, 2, 1, 3))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Flash attention.  q: (B, S, Hq, D); k/v: (B, S, Hkv, D) with
    Hq % Hkv == 0 (GQA).  Softmax scale is D**-0.5 (applied inside).
    ``window`` (causal only): a query sees its last ``window`` keys, its
    own among them, forward and backward; tiles wholly outside the band
    are neither fetched nor computed.

    On TPU a shape the kernel cannot tile (after the causal pad below)
    raises: silently running another implementation would hide that
    the kernel is off the path.
    """
    B, Sq, Hq, D = q.shape
    Sk = k.shape[1]
    if Hq % k.shape[2]:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={k.shape[2]}")
    if window is not None and not (causal and window > 0):
        raise ValueError("a window is a band under the causal diagonal")
    # Under a mesh the kernel runs per shard of the batch and head axes
    # (attention is independent across both); the sequence stays whole.
    q_axes = ("batch", None, "heads", "head_dim")
    kv_axes = ("batch", None, "kv_heads", "head_dim")
    flash = shard_over_mesh(
        functools.partial(_flash, causal=causal, block_q=block_q,
                          block_k=block_k, window=window),
        in_axes=(q_axes, kv_axes, kv_axes), out_axes=q_axes)
    if not _supported(Sq, Sk, D):
        if causal and Sq == Sk:
            # Pad the sequence up to a tileable length and slice the
            # result.  Exact for causal self-attention: valid query rows
            # (< Sq) can never attend to padded key columns (>= Sq)
            # because col > row is masked; padded query rows are garbage
            # but discarded by the slice.  Taken under interpret mode
            # too, so CPU tests cover the same pad+slice path TPUs run.
            s_pad = -Sq % LANES
            if _supported(Sq + s_pad, Sk + s_pad, D):
                pad = ((0, 0), (0, s_pad), (0, 0), (0, 0))
                out = flash(jnp.pad(q, pad), jnp.pad(k, pad),
                            jnp.pad(v, pad))
                return out[:, :Sq]
        if not _use_interpret():  # interpret mode tiles any shape
            raise ValueError(
                f"flash_attention cannot tile Sq={Sq}, Sk={Sk}, D={D} "
                f"on TPU (needs Sq % 8 == 0, Sk % {LANES} == 0 and D "
                f"<= {LANES} or a multiple of it); use "
                f"attention_impl='dot' for this shape")
    return flash(q, k, v)


def flash_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            scale: float, window: Optional[int] = None,
                            lse: bool = True,
                            keep: Optional[jax.Array] = None,
                            lengths: Optional[jax.Array] = None
                            ) -> jax.Array:
    """The forward alone, for a serving prefill: causal, a query seeing
    its last ``window`` keys where one is given (tiles outside the band
    are neither fetched nor computed).  q: (B, S, Hq, D); k/v: (B, S,
    Hkv, D), or v of a head width of its own (B, S, Hkv, Dv), positions
    0..S-1; ``scale`` multiplies the scores; ``lse=False`` leaves the
    softmax statistics, which only a backward pass reads, unwritten.
    ``keep``: int8 (B, S, S), nonzero where a query sees a key -- a learned
    selection (``models/indexer.py``): of the causal keys those alone, the
    tiles computed dense and masked.  ``lengths``: int32 (B,), the rows'
    real lengths in a padded bucket: a q block wholly past its row's
    length is neither fetched nor computed and comes back as zeros; every
    position before the length is what it is without ``lengths``, bit for
    bit (positions past it in the block it ends in: computed as before).
    The device trace shows the kernel under this function's name, with
    ``keep`` as ``sparse_prefill_attention``."""
    B, S, Hq, D = q.shape
    if Hq % k.shape[2]:
        raise ValueError(f"Hq={Hq} not a multiple of Hkv={k.shape[2]}")
    interpret = _use_interpret()
    # The forward alone also takes a head that is whole sublane tiles but
    # not whole lanes (latent attention's 192-wide q/k beside a 128-wide
    # v): Mosaic lays it out as 256 lanes (AOT for a v5e, PR 37).
    tiles = _supported(S, S, LANES if D % 64 == 0 else D)
    if not interpret and not tiles:
        raise ValueError(f"flash_prefill_attention cannot tile S={S}, "
                         f"D={D} on TPU")
    qt = jnp.transpose(q, (0, 2, 1, 3)) * jnp.asarray(scale, q.dtype)
    o, _lse = _fwd(qt, jnp.transpose(k, (0, 2, 1, 3)),
                   jnp.transpose(v, (0, 2, 1, 3)), causal=True,
                   block_q=None, block_k=None, interpret=interpret,
                   window=window, with_lse=lse, keep=keep, lengths=lengths,
                   name="flash_prefill_attention" if keep is None
                   else "sparse_prefill_attention")
    return jnp.transpose(o, (0, 2, 1, 3))


def flash_attention_causal(q, k, v, positions=None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           window: Optional[int] = None):
    """Drop-in for models.llama.dot_attention (standard causal layout;
    packed/offset positions must use the dot path).  ``block_q``/
    ``block_k`` override the kernel tile sizes (None: the default,
    ``DEFAULT_BLOCK``); ``window``: ``flash_attention``'s."""
    _check_default_positions(positions, q.shape[1], "flash_attention_causal")
    return flash_attention(q, k, v, causal=True, block_q=block_q,
                           block_k=block_k, window=window)


def _check_default_positions(positions, seq_len, name):
    """The flash kernels assume the standard causal layout
    positions == arange(seq).  Packed/offset positions would silently
    attend wrongly, so reject them instead of ignoring the argument."""
    if positions is None:
        return
    default = jnp.arange(seq_len, dtype=jnp.int32)
    pos = jnp.asarray(positions)
    if pos.ndim == 2:
        pos = pos[0]
    try:
        import numpy as np

        if pos.shape == default.shape and bool(np.all(
                np.asarray(pos) == np.asarray(default))):
            return
    except jax.errors.TracerArrayConversionError:
        # Under tracing we can't inspect values; trust the caller
        # (llama.forward only routes default layouts here).
        return
    raise NotImplementedError(
        f"{name} only supports the standard causal layout "
        "(positions == arange(seq_len)); use the dot-attention path "
        "for packed or offset positions")

"""A learned sparse-attention indexer in front of an attention layer
(DeepSeek-V3.2-Exp's "lightning indexer"; served only, dense plane).

An attending layer of a config with ``index_topk`` > 0 projects, beside q,
k and v, ``index_heads`` index queries ``qI_{t,j}`` of ``index_head_dim``,
ONE index key ``kI_t`` of that width (what the serving cache keeps of a
token beside K and V) and a weight a head ``w_{t,j}``.  Query t scores key
s <= t as

    I_{t,s} = sum_j w_{t,j} * relu(qI_{t,j} . kI_s)          (float32)

and attends the ``index_topk`` keys that score highest, every key while
there are no more than that.  The selection is EXACT, ties to the lower
position, and it is a MASK (``topk_keep``): the k-th largest score of a row
by bisection over the float's ordered bits -- 32 counting passes -- and
``>=`` it.  A prefill makes it ``(P, P)`` a query tile at a time
(``prefill_keep``; the (P, P) float32 scores never exist whole) for the
flash forward; a decode step ``(B, S)`` (``select``) for the decode
kernel.  Both attend the rows as they lie, masked: on a v5e a gather of
2,048 rows of 1 KB costs more than reading 16,384 in a piece (20 GB/s
against ~750; PERF.md section 6, PR 45).

Index keys are kept TRANSPOSED, positions along lanes -- ``(Di, S)`` a
slot and layer: a 64-wide minor dimension would be padded to 128 lanes,
and the scores' matmul reads ``(Hi, Di) @ (Di, S)`` as it lies.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

# Queries a prefill scores and selects at once (Keye-VL-2.0's
# ``q_chunk_size``): at 12,288 keys a tile's scores a head are 16 x 25 MB.
QUERY_TILE = 512
LEAVES = ("wq_idx", "wk_idx", "ww_idx")


def init_params(rng, c, layers: int, dense) -> Dict[str, jax.Array]:
    """The three index projections over ``layers`` attending layers: no
    bias, no norm of their own."""
    ks = jax.random.split(rng, 3)
    D, Hi, Di = c.hidden_size, c.index_heads, c.index_head_dim
    return {"wq_idx": dense(ks[0], (layers, D, Hi * Di), D),
            "wk_idx": dense(ks[1], (layers, D, Di), D),
            "ww_idx": dense(ks[2], (layers, D, Hi), D)}


def param_axes(c) -> Dict[str, Tuple]:
    return {name: ("layers", "embed", None) for name in LEAVES}


@jax.named_scope("indexer")
def project(h: jax.Array, layer: Dict[str, jax.Array], c):
    """h (B, S, D), the attention norm's output -> ``(qI (B, S, Hi, Di),
    kI (B, S, Di), w (B, S, Hi) float32)``."""
    from ray_tpu.models.llama import matmul

    B, S, _ = h.shape
    dt = c.dtype
    qi = matmul(h, layer["wq_idx"].astype(dt)).reshape(
        B, S, c.index_heads, c.index_head_dim)
    ki = matmul(h, layer["wk_idx"].astype(dt))
    w = matmul(h, layer["ww_idx"].astype(dt), jnp.float32)
    return qi, ki, w


@jax.named_scope("indexer")
def scores(qi: jax.Array, keys_t: jax.Array, w: jax.Array) -> jax.Array:
    """qi (B, T, Hi, Di), keys_t (B, Di, S) transposed, w (B, T, Hi) ->
    I (B, T, S) float32.  A score of -0.0 is given as +0.0: the two are one
    value, and a tie, to the selection."""
    s = jnp.einsum("btjd,bds->btjs", qi, keys_t.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    out = jnp.sum(jax.nn.relu(s) * w[..., None], axis=2)
    return jnp.where(out == 0, 0.0, out)


def _ordered(x: jax.Array) -> jax.Array:
    """float32 -> uint32 in the same order (-inf lowest)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    signed = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jax.lax.bitcast_convert_type(signed, jnp.uint32) \
        ^ jnp.uint32(0x80000000)


@jax.named_scope("index_select")
def topk_keep(x: jax.Array, k: int) -> jax.Array:
    """x (..., S) float32, -inf where a key is no candidate -> bool (...,
    S): the ``k`` largest candidates of each row, of equal ones the lower
    positions first; every candidate of a row that has no more than ``k``.

    The k-th largest value by bisection over the ordered bits (32 passes
    that count ``>=``); where more keys EQUAL it than the k has room for,
    a second bisection over the positions among them (taken only then)."""
    S = x.shape[-1]
    if S <= k:
        return x > -jnp.inf
    u = _ordered(x)
    lead = x.shape[:-1]

    def value_bit(i, v):
        cand = v | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[..., None], axis=-1,
                         dtype=jnp.int32) >= k
        return jnp.where(enough, cand, v)

    v = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros(lead, jnp.uint32))
    above = u > v[..., None]
    equal = u == v[..., None]
    room = k - jnp.sum(above, axis=-1, dtype=jnp.int32)       # >= 1
    pos = jnp.arange(S, dtype=jnp.int32)
    lowest = _ordered(jnp.asarray(-jnp.inf, jnp.float32))

    def first_equal():
        # the position of the ``room``-th equal key: the largest p with
        # fewer than ``room`` equal keys before it
        def position_bit(i, p):
            cand = p | (jnp.int32(1) << (bits - 1 - i))
            before = jnp.sum(equal & (pos < cand[..., None]), axis=-1,
                             dtype=jnp.int32)
            return jnp.where(before < room, cand, p)

        bits = max(1, (S - 1).bit_length())
        return jax.lax.fori_loop(0, bits, position_bit,
                                 jnp.zeros(lead, jnp.int32))

    crowded = (jnp.sum(equal, axis=-1, dtype=jnp.int32) > room) \
        & (v > lowest)
    last = jax.lax.cond(jnp.any(crowded), first_equal,
                        lambda: jnp.full(lead, S, jnp.int32))
    return (above | (equal & (pos <= last[..., None]))) & (x > -jnp.inf)


def prefill_keep(qi, ki_t, w, k: int, lengths=None) -> Optional[jax.Array]:
    """Which keys each query of a prompt attends, as int8 (B, P, P): query
    t sees key s iff s <= t and s is among the ``k`` highest ``I_{t,.}``
    over s <= t.  A ``QUERY_TILE`` of queries at a time.  None where the
    prompt is no longer than ``k``: every query sees every key before it.

    A launch of one row of whole tiles takes ``ops/index_select.py``'s
    kernel (by shape: ``index_select.engages``), which is told the rows'
    ``lengths`` (B,) and leaves the queries wholly past a row's length
    unselected, their rows of the mask zeros; every other shape XLA's form
    below, which selects for every query of the bucket."""
    from ray_tpu.ops import index_select

    B, P, Hi, Di = qi.shape
    if P <= k:
        return None
    if index_select.engages(B, P, k, Hi, Di, QUERY_TILE):
        if lengths is None:
            lengths = jnp.full((B,), P, jnp.int32)
        with jax.named_scope("index_select"):
            return index_select.prefill_keep(qi, ki_t, w, lengths, k,
                                             QUERY_TILE)
    tile = QUERY_TILE if P % QUERY_TILE == 0 else P
    key_pos = jnp.arange(P, dtype=jnp.int32)

    def one(args):
        q_tile, w_tile, first = args
        seen = key_pos[None, :] <= (first + jnp.arange(
            tile, dtype=jnp.int32))[:, None]
        score = jnp.where(seen[None], scores(q_tile, ki_t, w_tile),
                          -jnp.inf)
        return topk_keep(score, k).astype(jnp.int8)

    def tiles(a):
        return jnp.moveaxis(a.reshape((B, P // tile, tile) + a.shape[2:]),
                            1, 0)

    keep = jax.lax.map(one, (tiles(qi), tiles(w),
                             jnp.arange(0, P, tile, dtype=jnp.int32)))
    return jnp.moveaxis(keep, 0, 1).reshape(B, P, P)


def prefill_tiles(c, bucket: int, lengths) -> Optional[Tuple[str, int, int]]:
    """Host arithmetic for the engine's own account of a prefill group of
    ``len(lengths)`` rows x ``bucket`` positions (``lengths``: a row's real
    positions, 0 a padding row): ``(the form prefill_keep takes -- "kernel"
    or "xla" --, the group's query tiles a layer, those of them that start
    at or past their row's length and that the kernel therefore declines)``;
    XLA's form declines none.  None where the bucket is no longer than
    ``index_topk``: nothing is selected."""
    from ray_tpu.ops import index_select

    if bucket <= c.index_topk:
        return None
    rows = len(lengths)
    if not index_select.engages(rows, bucket, c.index_topk, c.index_heads,
                                c.index_head_dim, QUERY_TILE):
        tile = QUERY_TILE if bucket % QUERY_TILE == 0 else bucket
        return "xla", rows * (bucket // tile), 0
    tiles = bucket // QUERY_TILE
    run = sum(min(-(-int(n) // QUERY_TILE), tiles) for n in lengths)
    return "kernel", rows * tiles, rows * tiles - run


@jax.named_scope("index_select")
def select(score: jax.Array, n_valid: jax.Array, k: int) -> jax.Array:
    """One query a row: score (B, S) float32, of which the first
    ``n_valid`` (B,) are candidates -> bool (B, S), the ``min(n_valid, k)``
    keys the row attends.  A row with no more than ``k`` candidates attends
    them all."""
    candidates = jnp.arange(score.shape[1], dtype=jnp.int32)[None, :] \
        < n_valid[:, None]
    return topk_keep(jnp.where(candidates, score, -jnp.inf), k)

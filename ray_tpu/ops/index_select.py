"""A prefill's learned selection of keys for TPU (Pallas): the index scores
of a tile of queries, each row's ``k``-th largest score and the mask, with
the tile's scores held on the chip -- ``models/indexer.prefill_keep``'s
``scores`` + ``topk_keep`` a query tile, as one kernel.

Per row of the prompt and tile of ``tile`` queries (a grid step), for the
columns a result depends on -- keys ``[0, reach)``, ``reach`` the tile's
block of ``ROWS`` queries' last position + 1 rounded up to ``COLS``:

1. ``I = sum_j w_j relu(qI_j . kI)`` in float32, head after head on the
   MXU (a contraction of ``index_head_dim``) and the vector unit, a score of
   -0.0 made +0.0 (``indexer.scores``' equation), as the float's ORDERED
   bits (``indexer._ordered``: an unsigned integer in the same order);
2. those bits TURNED, a ``GROUP`` of 4,096 columns at a time: 32 chunks of
   128 columns become 32 bit planes, word ``[row, lane]`` of plane i holding
   bit i of the 32 keys at columns ``j 128 + lane`` (a 32 x 32 bit matrix a
   lane, five rounds of masked swaps on the vector unit).  The planes are
   what VMEM keeps of a tile: as many bytes as its scores;
3. the ``k``-th largest candidate of each row by ``topk_keep``'s bisection,
   32 passes from the top bit down, a pass over ONE plane: with ``eq`` the
   keys that equal the value so far in the bits above (at first: the
   candidates, the keys at or before the query) and ``gt`` those already
   greater, ``count(>= value | bit) = |gt| + popcount(eq & plane)`` -- a
   word is 32 keys, so a pass is 1/32 of a compare a key;
4. the mask ``gt | eq``.  Where a row has more keys EQUAL to its ``k``-th
   than the ``k`` has room for, the lower positions first, by a second
   bisection over the positions of ``eq`` -- taken only where a tile has
   such a row before its row's length;
5. ``keep`` int8 ``(tile, P)`` written once, a bit a key: zeros at and past
   ``reach``.

Nothing of ``(tile, heads, P)`` or ``(tile, P)`` float32 goes through HBM:
the operands are read once and ``keep`` written once, ``P^2`` bytes a layer
where XLA's form moves 32 x 4 x ``P^2`` (PERF.md section 6, PR 66).

The kernel is told the rows' ``lengths``.  A query tile that starts at or
past its row's length runs no step of 1-4 and fetches nothing new, and a
block of ``ROWS`` queries that does likewise: their rows of ``keep`` are
ZEROS.  A row of zeros attends nothing, which the masked flash forward
behind it survives: its q block of 1,024 rows runs when it STARTS before
the length, so it reads such rows where it straddles the row's end; a
masked score there is the finite ``NEG_INF``, the row's softmax is uniform
over its tile and the result finite (and read by nothing).  Rows past the
length inside a block that runs are selected like any other, but are never
a reason for the second bisection (padding tokens are one token: plateaus).

Held to ``indexer.topk_keep`` on the same scores bit for bit
(tests/test_index_select.py).  The scores are the same float32 equation; the
MXU's order inside a contraction and the heads' order of summation (0 ...
``heads - 1`` here) are not XLA's fusion's, so a near-tie at the ``k``-th
place may fall the other way.

Engages by shape (``engages``): ONE row a launch (a program of several rows
with a Mosaic body did not return on the chip: PERF.md section 6 (g), PR
62; cell 10's prompts are a row a launch), a prompt of whole tiles longer
than ``k``, index heads of whole half-vregs.  Everything else keeps XLA's
form, which is also the oracle.  Interpret mode runs it on the CPU for its
own tests, decided as ``ops/decode_attention.py`` decides.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_flash = importlib.import_module("ray_tpu.ops.flash_attention")
LANES = _flash.LANES
# Queries scored, turned and counted together, whose reach bounds the
# columns they visit (a word of a plane is one (ROWS, 128) block a group).
ROWS = 128
# Columns a step of the score and the write loops.
# tools/index_select_sweep.py read both last (PERF.md section 6, PR 66).
COLS = 512
# The longest prompt whose tile of bit planes (tile x P x 4 B), two mask
# blocks and operands fit the VMEM the call asks for.
MAX_KEYS = 16384
# Columns whose ordered bits are kept as 32 bit planes of one word a lane:
# 32 chunks of 128 columns, bit j of a lane's word chunk j's key.
GROUP = 32 * 128
STAGES = ("scores", "count", "write")
_I32 = jnp.int32
_MIN = -2 ** 31


def engages(rows: int, keys: int, k: int, heads: int, head_dim: int,
            tile: int) -> bool:
    """Whether the kernel takes a launch of ``rows`` rows of ``keys``
    positions; ``indexer``'s XLA form otherwise."""
    return (rows == 1 and k < keys <= MAX_KEYS and keys % tile == 0
            and tile % ROWS == 0 and tile % COLS == 0 and GROUP % COLS == 0
            and head_dim % (LANES // 2) == 0
            and (heads * head_dim) % LANES == 0)


def _transposed(x):
    """32 int32 arrays -> 32: bit j of result i is bit i of ``x[j]`` (a 32 x
    32 bit matrix turned a lane: five rounds of masked swaps)."""
    x = list(x)
    s, m = 16, 0x0000FFFF
    while s:
        mask = _I32(m - 2 ** 32 if m >= 2 ** 31 else m)
        for a in range(32):
            if a & s:
                continue
            t = (jax.lax.shift_right_logical(x[a], _I32(s)) ^ x[a + s]) & mask
            x[a + s] = x[a + s] ^ t
            x[a] = x[a] ^ jnp.left_shift(t, _I32(s))
        s >>= 1
        m = (m ^ (m << s)) & 0xFFFFFFFF
    return x


def _kernel(lens_ref, q_ref, w_ref, k_ref, keep_ref, stage_ref, planes_ref,
            eq_ref, gt_ref, wb_ref, cgt_ref, cge_ref, acc_ref, last_ref, *,
            k, heads, head_dim, tile, stages, ROWS, COLS):
    P = keep_ref.shape[1]
    blocks = tile // ROWS
    groups_max = -(-P // GROUP)
    steps_a_group = GROUP // COLS
    length = lens_ref[pl.program_id(0)]
    first = pl.program_id(1) * tile
    lane = jax.lax.broadcasted_iota(_I32, (ROWS, LANES), 1)

    def rows_of(rb):
        return pl.ds(pl.multiple_of(rb * ROWS, ROWS), ROWS)

    def steps(rb):
        """Steps of ``COLS`` columns that block rb's queries reach; none
        where the block starts at or past the row's length."""
        reach = first + (rb + 1) * ROWS
        return jnp.where(first + rb * ROWS < length,
                         (reach + COLS - 1) // COLS, 0)

    def each_block(body):
        def step(rb, carry):
            body(rb, rows_of(rb), steps(rb))
            return carry

        jax.lax.fori_loop(0, blocks, step, 0)

    def each_group(rb, n, body, carry=0):
        """``body(g, index of (rb, g) in the group scratch, carry)`` over
        the groups of ``GROUP`` columns that hold block rb's ``n`` steps."""
        return jax.lax.fori_loop(
            0, (n + steps_a_group - 1) // steps_a_group,
            lambda g, carry: body(g, rb * groups_max + g, carry), carry)

    def low_bits(n):
        """int32 words whose ``clip(n, 0, 32)`` lowest bits are set."""
        return jnp.where(n >= 32, _I32(-1),
                         jnp.left_shift(_I32(1), jnp.clip(n, 0, 31)) - 1)

    def before(g, bound):
        """Group g's words with a bit set where the key's column is under
        ``bound`` (ROWS, LANES): bit j of lane l is column g GROUP + j LANES
        + l."""
        return low_bits(jax.lax.shift_right_arithmetic(
            bound - g * GROUP - lane + (LANES - 1), _I32(7)))

    # ------------------------------------------------------------ 1. scores
    def score(rb, rows, n):
        for j in range(heads):
            wb_ref[j] = jnp.broadcast_to(w_ref[rows, j:j + 1], (ROWS, LANES))

        def group(g, at, carry):
            def step(c, carry):
                col = pl.multiple_of((g * steps_a_group + c) * COLS, COLS)
                keys = k_ref[:, pl.ds(col, COLS)].astype(q_ref.dtype)
                acc = [jnp.zeros((ROWS, LANES), jnp.float32)
                       for _ in range(COLS // LANES)]
                for j in range(heads):
                    s = jnp.dot(q_ref[rows, j * head_dim:(j + 1) * head_dim],
                                keys, preferred_element_type=jnp.float32)
                    for a in range(COLS // LANES):
                        acc[a] = acc[a] + jnp.maximum(
                            s[:, a * LANES:(a + 1) * LANES], 0.0) * wb_ref[j]
                for a in range(COLS // LANES):
                    x = jnp.where(acc[a] == 0, 0.0, acc[a])
                    bits = jax.lax.bitcast_convert_type(x, _I32)
                    # the float's order as an unsigned integer's
                    stage_ref[:, pl.ds(pl.multiple_of(
                        c * COLS + a * LANES, LANES), LANES)] = jnp.where(
                            bits < 0, ~bits, bits ^ _I32(_MIN))
                return carry

            jax.lax.fori_loop(
                0, jnp.minimum(n - g * steps_a_group, steps_a_group), step, 0)

            # the group's 32 chunks of 128 columns -> its 32 bit planes, 8
            # queries (a vreg a chunk) at a time
            def turn(r, carry):
                eight = pl.ds(pl.multiple_of(r * 8, 8), 8)
                planes = _transposed([
                    stage_ref[eight, j * LANES:(j + 1) * LANES]
                    for j in range(32)])
                for i in range(32):
                    planes_ref[at * 32 + i, eight, :] = planes[i]
                return carry

            jax.lax.fori_loop(0, ROWS // 8, turn, 0)
            return carry

        if "scores" in stages:
            each_group(rb, n, group)

    # ---------------------------------------------- 2. the k-th largest score
    def start(rb, rows, n):
        def group(g, at, carry):
            # the candidates: the keys at or before the query
            eq_ref[at] = before(g, first + rb * ROWS + 1
                                + jax.lax.broadcasted_iota(
                                    _I32, (ROWS, LANES), 0))
            gt_ref[at] = jnp.zeros((ROWS, LANES), _I32)
            return carry

        each_group(rb, n, group)

    def value_bit(i, carry):
        """Bit 31 - i of the k-th largest: ``eq`` the keys that equal it in
        the bits above, ``gt`` those already greater."""
        def count(rb, rows, n):
            acc_ref[rows, :] = each_group(
                rb, n, lambda g, at, acc: acc + jax.lax.population_count(
                    eq_ref[at] & planes_ref[at * 32 + 31 - i]),
                jnp.zeros((ROWS, LANES), _I32))

        each_block(count)
        cgt = cgt_ref[...]
        # a row's sum on every lane, by the MXU: a lane's count is at most
        # 32 a group, whole numbers that bfloat16 holds exactly (7 cross-lane
        # rotations a vreg were most of a pass)
        cnt = cgt + jnp.dot(
            acc_ref[...].astype(jnp.float32).astype(jnp.bfloat16),
            jnp.ones((LANES, LANES), jnp.bfloat16),
            preferred_element_type=jnp.float32).astype(_I32)
        enough = cnt >= k
        cge_ref[...] = jnp.where(enough, cnt, cge_ref[...])
        cgt_ref[...] = jnp.where(enough, cgt, cnt)
        # acc: all ones where the bit is NOT taken
        acc_ref[...] = jnp.where(enough, _I32(0), _I32(-1))

        def settle(rb, rows, n):
            def group(g, at, carry):
                eq, plane = eq_ref[at], planes_ref[at * 32 + 31 - i]
                left = acc_ref[rows, :]
                gt_ref[at] = gt_ref[at] | (eq & plane & left)
                eq_ref[at] = eq & (plane ^ left)
                return carry

            each_group(rb, n, group)

        each_block(settle)
        return carry

    # ------------------------------------------------------------ 3. the mask
    def write(tied):
        def block(rb, rows, n):
            def group(g, at, carry):
                eq = eq_ref[at]
                if tied:
                    eq = eq & before(g, last_ref[rows, :] + 1)
                words = gt_ref[at] | eq

                def step(c, carry):
                    for a in range(COLS // LANES):
                        col = pl.multiple_of(
                            (g * steps_a_group + c) * COLS + a * LANES, LANES)
                        keep_ref[rows, pl.ds(col, LANES)] = (
                            jax.lax.shift_right_logical(
                                words, jnp.broadcast_to(
                                    c * (COLS // LANES) + a, words.shape))
                            & 1).astype(jnp.int8)
                    return carry

                jax.lax.fori_loop(
                    0, jnp.minimum(n - g * steps_a_group, steps_a_group),
                    step, 0)
                return carry

            if "write" in stages:
                each_group(rb, n, group)

            def blank(c, carry):
                keep_ref[rows, pl.ds(pl.multiple_of(c * COLS, COLS), COLS)] \
                    = jnp.zeros((ROWS, COLS), jnp.int8)
                return carry

            jax.lax.fori_loop(n, P // COLS, blank, 0)

        each_block(block)

    def ties():
        """``last_ref``: the position of a row's last equal key that the
        ``k`` has room for -- the largest p with fewer than ``room`` equal
        keys before it."""
        last_ref[...] = jnp.zeros_like(last_ref)

        def position_bit(i, carry):
            bit = jnp.left_shift(_I32(1), max(1, (P - 1).bit_length()) - 1 - i)

            def block(rb, rows, n):
                cand = last_ref[rows, :] | bit
                under = each_group(
                    rb, n, lambda g, at, acc: acc + jax.lax.population_count(
                        eq_ref[at] & before(g, cand)),
                    jnp.zeros((ROWS, LANES), _I32))
                room = k - cgt_ref[rows, :]
                last_ref[rows, :] = jnp.where(
                    jnp.sum(under, axis=1, keepdims=True) < room, cand,
                    last_ref[rows, :])

            each_block(block)
            return carry

        jax.lax.fori_loop(0, max(1, (P - 1).bit_length()), position_bit, 0)

    @pl.when(first >= length)
    def _declined():
        keep_ref[...] = jnp.zeros_like(keep_ref)

    @pl.when(first < length)
    def _selected():
        each_block(score)
        each_block(start)
        cgt_ref[...] = jnp.zeros_like(cgt_ref)
        cge_ref[...] = jnp.zeros_like(cge_ref)
        if "count" in stages:
            jax.lax.fori_loop(0, 32, value_bit, 0)
        row = first + jax.lax.broadcasted_iota(_I32, (tile, LANES), 0)
        # more keys >= the k-th largest than k: some EQUAL it that the k has
        # no room for.  (A build of one stage alone, the sweep's, counts
        # what VMEM happens to hold: never a reason for the ties.)
        crowded = (cge_ref[...] > k) & (row < length)
        any_crowded = (jnp.max(crowded.astype(_I32)) > 0) \
            & (len(stages) == len(STAGES))

        @pl.when(jnp.logical_not(any_crowded))
        def _plain():
            write(tied=False)

        @pl.when(any_crowded)
        def _tied():
            ties()
            write(tied=True)


@functools.partial(jax.jit, static_argnames=(
    "k", "heads", "tile", "stages", "rows", "cols", "interpret"))
def _call(lens, q, w, keys_t, *, k, heads, tile, stages, rows, cols,
          interpret):
    """The kernel over q (B, P, heads x head_dim), w (B, P, heads) float32,
    keys_t (B, head_dim, P): P whole tiles.  Jitted, so that the layers of
    a program share one lowering of it."""
    B, P, width = q.shape
    head_dim = width // heads

    def running(b, i, lens):
        # a declined tile fetches nothing new: the index stays
        return jnp.minimum(i, jnp.maximum(lens[b] - 1, 0) // tile)

    groups = tile // rows * -(-P // GROUP)
    vmem = (2 * tile * width * q.dtype.itemsize + 2 * tile * LANES * 4
            + 2 * head_dim * P * keys_t.dtype.itemsize + 2 * tile * P
            + (groups * 34 * rows * LANES + rows * GROUP) * 4
            + (heads * rows + 4 * tile) * LANES * 4)
    return pl.pallas_call(
        functools.partial(_kernel, k=k, heads=heads, head_dim=head_dim,
                          tile=tile, stages=stages, ROWS=rows, COLS=cols),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, P // tile),
            in_specs=[
                pl.BlockSpec((None, tile, width),
                             lambda b, i, lens: (b, running(b, i, lens), 0)),
                pl.BlockSpec((None, tile, heads),
                             lambda b, i, lens: (b, running(b, i, lens), 0)),
                pl.BlockSpec((None, head_dim, P),
                             lambda b, i, lens: (b, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, tile, P),
                                   lambda b, i, lens: (b, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, GROUP), _I32),      # a group's ordered bits
                pltpu.VMEM((groups * 32, rows, LANES), _I32),   # bit planes
                pltpu.VMEM((groups, rows, LANES), _I32),  # equal so far
                pltpu.VMEM((groups, rows, LANES), _I32),  # greater already
                pltpu.VMEM((heads, rows, LANES), jnp.float32),  # w, by lanes
                pltpu.VMEM((tile, LANES), _I32),      # how many are greater
                pltpu.VMEM((tile, LANES), _I32),      # how many are >= it
                pltpu.VMEM((tile, LANES), _I32),      # a pass's counts
                pltpu.VMEM((tile, LANES), _I32),      # ties: the last position
            ]),
        out_shape=jax.ShapeDtypeStruct((B, P, P), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem + (16 << 20)),
        interpret=interpret, name="index_select_prefill",
    )(lens, q, w, keys_t)


def prefill_keep(qi: jax.Array, keys_t: jax.Array, w: jax.Array,
                 lengths: jax.Array, k: int, tile: int) -> jax.Array:
    """qi (B, P, heads, head_dim), keys_t (B, head_dim, P) transposed, w (B,
    P, heads) float32, lengths (B,) a row's real positions -> int8 (B, P,
    P): query t sees key s iff s <= t and s is among the ``k`` highest
    ``I_{t,.}`` over s <= t, of equal ones the lower positions first.  Rows
    of a block of ``ROWS`` queries that starts at or past its row's length
    are zeros.  The shape must ``engage``."""
    B, P, heads, head_dim = qi.shape
    return _call(jnp.minimum(lengths, P).astype(_I32),
                 qi.reshape(B, P, heads * head_dim), w.astype(jnp.float32),
                 keys_t, k=k, heads=heads, tile=tile, stages=STAGES, rows=ROWS,
                 cols=COLS, interpret=_flash._use_interpret())

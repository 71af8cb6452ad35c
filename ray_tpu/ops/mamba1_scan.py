"""The Mamba-1 recurrence over a prompt for TPU (Pallas):
``models/mamba1.prefill``'s scan with a block of channels' state held on the
chip from a row's first position to its last.

Per position, channel and state dimension n of N, all float32:

    S_t[n] = exp(dt_t * A[n]) * S_{t-1}[n] + (dt_t * u_t) * B_t[n]
    y_t    = sum_n S_t[n] * C_t[n] + D * u_t

(``exp`` as the chip has it, a power of two: ``A log2(e)`` is handed in, a
multiply a state vreg and position less than ``exp``'s own lowering).

The grid walks (row, block of ``CHANNELS`` channels, block of ``POSITIONS``
positions), the positions innermost and in order.  A block of 1,024 channels
is ONE ``(8, 128)`` vreg a state dimension, so the state of a block is N
vregs: a loop carry inside a block of positions, the result's resident block
between blocks, written to HBM once a (row, block of channels).  ``B_t`` and
``C_t`` are 2 N scalars a position, read from SMEM; ``y_t`` is N
multiply-adds of whole vregs, no reduce across lanes.

``u``, ``dt`` and ``y`` live in HBM as ``(P, Di)``: a tile is 8 positions of
128 channels, where the recurrence wants a position's 1,024 channels as one
vreg.  The kernel turns them itself, through VMEM scratch of 128-lane rows
and the load / store unit's sublane stride: tile (8 positions, channels
128 j ...) goes to rows ``8 t + j`` (a store of stride 8), position t is
then rows ``8 t ... 8 t + 7`` (one aligned load), and ``y`` comes back the
same way.  No array is turned in HBM, and the vector unit sees none of it.

A row is walked to its length in whole groups of 8 positions, not to the
bucket's end: ``dt`` is 0 at a padded position, which neither decays the
state nor feeds it, so the state is that of the row's last real position
either way; ``y`` past the last group walked is written as zeros (no real
position reads it).  The blocks of inputs past a row's length are not
fetched (their index stays at the row's last block).

Channels that are not whole blocks (the toy presets) keep
``models/mamba1.selective_scan``, XLA's loop, by shape (``engages``): padded
to a block they would go through the kernel too, but interpreted on the CPU
the kernel costs the phi-4 family's tests 160 s of lowering (PERF.md section
6, PR 62).  Interpret mode runs it on the CPU for its own tests, decided as
``ops/decode_attention.py`` decides.

The kernel takes a launch of ONE row (every launch of the cell that times
it: a bucket of 4,096 positions or more is a row a launch).  A prefill
program of several rows with the call in it did not return on the chip (4
rows x 512, 4 x 1,024) -- one call over the rows or a call a row, this body
or one that only writes zeros, so it is the program as XLA lays it out
around a Mosaic call and not the kernel's code; why is not found -- where
every program of one row returned, at every bucket.  A group of several rows
keeps XLA's loop, the program it had (PERF.md section 6 (g), PR 62).  The
call asks for no scoped VMEM of its own: its tiles and turned copies are 9.7
MB, inside the 16 MiB every fusion of a program has.
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
SUBLANES = 8
# Channels of a block (one vreg a state dimension) and positions a grid
# step: its tiles of u, dt and y, double buffered, and the three turned
# copies, 9 MB of VMEM (twice that at 512: past the default scoped limit).
# tools/mamba1_scan_sweep.py read them last (PERF.md section 6, PR 62).
CHANNELS = SUBLANES * LANES
POSITIONS = 256
_F32 = jnp.float32
_LOG2E = 1.4426950408889634


def engages(channels: int, rows: int) -> bool:
    """Whether the kernel takes a launch of ``rows`` rows of ``channels``
    channels; ``selective_scan``'s XLA form otherwise."""
    return channels % CHANNELS == 0 and rows == 1


def padded_len(P: int) -> int:
    """The positions the kernel's grid covers for a bucket of ``P``: whole
    blocks of ``POSITIONS`` (a shorter bucket: one block of whole groups
    of 8)."""
    block = min(POSITIONS, -(-P // SUBLANES) * SUBLANES)
    return -(-P // block) * block


@jax.jit
def _position(t, S, A, D, ut_ref, dtt_ref, yt_ref, bc_ref):
    """Position t of a block of positions, for a block of channels: S, A N
    vregs each, D a vreg; the turned ``u`` and ``dt`` are read and the
    turned ``y`` written at rows ``8 t ...``, ``B_t`` and ``C_t`` read as 2 N
    scalars from ``bc_ref`` -> S.  A jit of its own, refs and all: a process
    traces these ~250 operations once, not 8 times a bucket and program (a
    start's seconds: PERF.md section 6, PR 62)."""
    N = len(S)
    rows = pl.ds(pl.multiple_of(t * SUBLANES, SUBLANES), SUBLANES)
    u, dt = ut_ref[rows, :], dtt_ref[rows, :]
    fed = dt * u
    y = D * u
    at = t * (2 * N)
    after = []
    for n in range(N):
        after.append(jnp.exp2(dt * A[n]) * S[n] + fed * bc_ref[0, at + n])
        y = y + after[n] * bc_ref[0, at + N + n]
    yt_ref[rows, :] = y
    return tuple(after)


def _kernel(lens_ref, u_ref, dt_ref, bc_ref, a_ref, d_ref, y_ref, s_ref,
            ut_ref, dtt_ref, yt_ref, *, N, block):
    g, p = pl.program_id(0), pl.program_id(2)
    groups = block // SUBLANES
    # groups of 8 positions of this block that hold a real position
    live = jnp.clip(
        (lens_ref[g] - p * block + SUBLANES - 1) // SUBLANES, 0, groups)

    @pl.when(p == 0)
    def _first_block():
        s_ref[...] = jnp.zeros_like(s_ref)

    def tiles(i):
        """Of group i's 8 positions: (their rows of a ``(P, Di)`` block,
        per tile j of 128 channels its lanes there and its rows -- ``8 t +
        j``, a stride of 8 -- of a turned copy)."""
        at = pl.ds(pl.multiple_of(i * SUBLANES, SUBLANES), SUBLANES)
        return at, [
            (slice(j * LANES, (j + 1) * LANES),
             pl.ds(i * SUBLANES * SUBLANES + j, SUBLANES, stride=SUBLANES))
            for j in range(SUBLANES)]

    def turn_in(i, _):
        at, turned = tiles(i)
        for lanes, rows in turned:
            ut_ref[rows, :] = u_ref[at, lanes]
            dtt_ref[rows, :] = dt_ref[at, lanes]
        return 0

    jax.lax.fori_loop(0, live, turn_in, 0)

    A = tuple(a_ref[n] for n in range(N))
    D = d_ref[...]

    def walk(i, S):
        # 8 positions in one block of code: the bundles fill (41 a
        # position; 71 with a loop around each)
        for k in range(SUBLANES):
            S = _position(i * SUBLANES + k, S, A, D, ut_ref, dtt_ref, yt_ref,
                          bc_ref)
        return S

    S = jax.lax.fori_loop(0, live, walk, tuple(s_ref[n] for n in range(N)))
    for n in range(N):
        s_ref[n] = S[n]

    def turn_out(i, _):
        at, turned = tiles(i)
        for lanes, rows in turned:
            y_ref[at, lanes] = yt_ref[rows, :]
        return 0

    jax.lax.fori_loop(0, live, turn_out, 0)

    def blank(i, _):
        y_ref[tiles(i)[0], :] = jnp.zeros((SUBLANES, CHANNELS), _F32)
        return 0

    jax.lax.fori_loop(live, groups, blank, 0)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _call(lens, u, dt, bc, A, D, *, block, interpret):
    """The kernel over u, dt (G, P, Di), bc (G, P / block, 1, block 2 N),
    A log2(e) (N, Di / 128, 128), D (Di / 128, 128): P whole blocks, Di
    whole blocks of ``CHANNELS``.  Jitted, so that the layers of a program
    share one lowering of it."""
    G, P, Di = u.shape
    N = A.shape[0]

    def last(g, p, lens):
        # a block past the row's length is not fetched: the index stays
        return jnp.minimum(p, jnp.maximum(lens[g] - 1, 0) // block)

    tile = pl.BlockSpec((None, block, CHANNELS),
                        lambda g, c, p, lens: (g, last(g, p, lens), c))
    return pl.pallas_call(
        functools.partial(_kernel, N=N, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(G, Di // CHANNELS, P // block),
            in_specs=[
                tile, tile,
                pl.BlockSpec((None, None, 1, block * 2 * N),
                             lambda g, c, p, lens: (g, last(g, p, lens), 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec((N, SUBLANES, LANES),
                             lambda g, c, p, lens: (0, c, 0)),
                pl.BlockSpec((SUBLANES, LANES),
                             lambda g, c, p, lens: (c, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, block, CHANNELS),
                             lambda g, c, p, lens: (g, p, c)),
                pl.BlockSpec((None, N, SUBLANES, LANES),
                             lambda g, c, p, lens: (g, 0, c, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((block * SUBLANES, LANES), _F32)] * 3,
        ),
        out_shape=[jax.ShapeDtypeStruct((G, P, Di), _F32),
                   jax.ShapeDtypeStruct((G, N, Di // LANES, LANES), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name="mamba1_scan",
    )(lens, u, dt, bc, A, D)


def mamba1_scan(u: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                C: jax.Array, D: jax.Array, lengths: jax.Array, chunk: int):
    """The recurrence over P positions from a zero state.  u, dt (G, P, Di)
    and B, C (G, P, N) float32, dt 0 at a row's padded positions; A (N, Di)
    float32, negative; D (Di,) float32; lengths (G,) a row's real positions.
    -> (y (G, P, Di) float32 WITH the ``D u`` term, the state after a row's
    last position (G, N, Di) float32).  Where the channels ``engage``, y is
    zeros past the group of 8 positions that holds a row's last one; where
    not, ``selective_scan``'s own, ``chunk`` positions an iteration."""
    G, P, Di = u.shape
    N = A.shape[0]
    if not engages(Di, G):
        from ray_tpu.models.mamba1 import selective_scan

        y, S = selective_scan(u, dt, A, B, C, chunk)
        return y + D * u, S
    Pp = padded_len(P)
    block = min(POSITIONS, Pp)
    ahead = ((0, 0), (0, Pp - P), (0, 0))
    y, S = _call(
        jnp.minimum(lengths, P).astype(jnp.int32),
        jnp.pad(u, ahead), jnp.pad(dt, ahead),
        jnp.pad(jnp.concatenate([B, C], -1), ahead).reshape(
            G, Pp // block, 1, block * 2 * N),
        (A * _LOG2E).reshape(N, Di // LANES, LANES),
        D.reshape(Di // LANES, LANES),
        block=block, interpret=_flash._use_interpret())
    return y[:, :P], S.reshape(G, N, Di)

"""The chunked gated delta rule of a KDA layer's prefill for TPU (Pallas):
``models/kda.chunk_rule``'s whole body for a block of heads, chunk after
chunk, with everything between the inputs and ``o`` held in VMEM.

Per chunk of ``C`` positions and head, with ``G`` the log-decay summed from
the chunk's start (``models/kda.py``'s docstring derives the form):

    A_ij = b_i sum_c k_ic k_jc e^(G_ic - G_jc)   j < i
    (I + A) [W | Y] = Diag(b) [V | e^G (.) K]
    U = W - Y S;   o_i = S^T (e^G_i (.) q_i) + sum_{j<=i} P_ij u_j
    S <- Diag(e^G_C) S + (e^(G_C - G) (.) K)^T U

The grid walks (row, block of ``_HEADS`` heads, chunk), the chunks innermost
and in order: a head's ``(d, d)`` state is read from the ``state`` operand
at the first chunk, carried in the result's VMEM block from chunk to chunk
and written out after the last.  q, k, v, g are read as ``(C, d)`` tiles at
lane offset ``h d`` of the ``(N, T, H d)`` arrays the layer's projections
produce, and ``o`` is written the same way: no array is turned in HBM.

A decay enters only as ``exp`` of a difference that is <= 0, in sub-blocks
of ``_SUB`` rows: a sub-block against the rows before it through its own
first row (two factors <= 1 and a matmul), against itself pairwise
(``_diagonal``: a column's products over the channels, a lane reduce a
tile).  ``W - Y S`` is ``(I + A)^-1 (b v - (b e^G k) S)``, so the state is
read once for it and for q, and the solve is ONE forward substitution
(``_forward_substitute``): blocks of ``_SOLVE`` rows in order, a matmul
between blocks and a row at a time inside one, on the vector unit -- a
matmul of 64 rows costs the matrix unit a pass over a 128 x 128 tile however
few its rows, and ten of them in a chain (the inverse level by level) cost
more than all the rest (PERF.md section 6, PR 56).  Every matmul takes
float32 operands at ``HIGHEST``.

A shape Mosaic cannot tile (``d`` not whole 128-lane tiles, a chunk that is
not whole ``_SUB``-row sub-blocks: the toy presets) keeps ``chunk_rule``'s
XLA form, by shape (``engages``); interpret mode runs the kernel on the CPU
for the test suite, decided as ``ops/decode_attention.py`` decides.
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
# Rows of a sub-block of the decayed products (a chunk is whole sub-blocks)
# and of a block of the forward substitution; heads of a row a grid step
# takes: their tiles of q, k, v, g and o, double buffered, and their states.
# tools/kda_chunk_sweep.py read them last (PERF.md section 6, PR 56).
_SUB = 16
_SOLVE = 16
_HEADS = 4
_VMEM_LIMIT = 32 << 20
_HIGHEST = jax.lax.Precision.HIGHEST
_F32 = jnp.float32


def engages(d: int, chunk: int) -> bool:
    """Whether the kernel takes a state of ``(d, d)`` in chunks of
    ``chunk``; ``chunk_rule``'s XLA form otherwise."""
    return d % LANES == 0 and chunk % max(_SUB, _SOLVE) == 0 and chunk < d


def _heads_a_block(heads: int) -> int:
    return max(n for n in range(1, min(_HEADS, heads) + 1)
               if heads % n == 0)


def _dot(a, b):
    return jnp.dot(a, b, precision=_HIGHEST, preferred_element_type=_F32)


def _dot_nt(a, b):
    """a (M, K), b (N, K) -> a b^T (M, N)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HIGHEST,
                               preferred_element_type=_F32)


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _diagonal(x2, kb, Gb, base, acc):
    """A sub-block against itself: ``acc`` (2, sub, C) gains, at lanes
    ``base + j``, ``sum_c x_ic k_jc e^(G_ic - G_jc)`` for ``j <= i`` of
    the sub-block's rows, for both x of ``x2`` (2, sub, d).  kb, Gb (sub,
    d).  Eight columns at a time, over the rows from their 8-row tile on:
    the tiles above hold nothing of them."""
    sub, C = acc.shape[1:]
    lanes = _iota((1, 1, C), 2)
    done = []
    for lo in range(0, sub, 8):
        rows = _iota((sub - lo, 1), 0) + lo
        x, G = x2[:, lo:], Gb[lo:]
        for j in range(lo, lo + 8):
            fall = jnp.exp(jnp.where(rows >= j, G - Gb[j:j + 1], -jnp.inf))
            col = jnp.sum(x * (kb[j:j + 1] * fall), axis=-1, keepdims=True)
            acc = jnp.where(lanes == base + j, col, acc)
        done.append(acc[:, :8])
        acc = acc[:, 8:]
    return jnp.concatenate(done, 1)


def _decayed_products(q, k, G, sub):
    """``M_x[i, j] = sum_c x_ic k_jc exp(G_ic - G_jc)`` for x = k and x = q,
    (2, C, C), exact where j <= i and zero above that diagonal."""
    C, d = G.shape
    at = _iota((C, 1), 0)
    blocks = []
    for I in range(C // sub):
        rows = slice(I * sub, (I + 1) * sub)
        kb, Gb = k[rows], G[rows]
        first = Gb[:1]
        x2 = jnp.stack([kb, q[rows]])
        if I:
            # against the rows before the sub-block, through its first row
            kj = k * jnp.exp(jnp.where(at < I * sub, first - G, -jnp.inf))
            off = _dot_nt((x2 * jnp.exp(Gb - first)).reshape(2 * sub, d),
                          kj).reshape(2, sub, C)
        else:
            off = jnp.zeros((2, sub, C), _F32)
        blocks.append(_diagonal(x2, kb, Gb, I * sub, off))
    return jnp.concatenate(blocks, axis=1)


def _running_sum(g):
    """The sum of g (C, d) down the rows, in log2(C) rotations."""
    C = g.shape[0]
    rows = _iota((C, 1), 0)
    s = 1
    while s < C:
        g = g + jnp.where(rows >= s, pltpu.roll(g, s, 0), 0.0)
        s *= 2
    return g


def _forward_substitute(A, R, solve):
    """``(I + A)^-1 R`` of a strictly lower triangular A (C, C) and R (C,
    d): blocks of ``solve`` rows in order, a block's rows less what the
    blocks before it weigh (a matmul), then a row at a time inside the
    block -- row j is final once rows < j are taken out of it, and the
    8-row tiles above row j's are no longer touched."""
    C, d = R.shape
    n = min(solve, C)
    solved = []
    for at in range(0, C, n):
        rest = R[at:at + n]
        if at:
            rest = rest - _dot(A[at:at + n], jnp.concatenate(
                solved + [jnp.zeros((C - at, d), _F32)], 0))
        for lo in range(0, n, 8):
            for j in range(lo, min(lo + 8, n - 1)):
                rest = rest - A[at + lo:at + n, at + j:at + j + 1] \
                    * rest[j - lo:j - lo + 1]
            solved.append(rest[:8])
            rest = rest[8:]
    return jnp.concatenate(solved, 0)


@functools.partial(jax.jit, static_argnames=("sub", "solve"))
def _one_head(q, k, v, g, b, S, *, sub, solve):
    """One chunk of one head: q, k, v, g (C, d), b (C, 1), S (d, d) ->
    (o (C, d), the state after the chunk).  A jit of its own: a kernel
    traces these ~1,000 operations once a process, whatever the heads a
    step, the layers and the programs that call it (a start's seconds:
    PERF.md section 6, PR 56)."""
    C, d = q.shape
    G = _running_sum(g)
    Mk, Mq = _decayed_products(q, k, G, sub)
    A = jnp.where(_iota((C, C), 0) > _iota((C, C), 1), b * Mk, 0.0)
    grown = jnp.exp(G)
    # U = W - Y S = T (b v - (b e^G k) S): one pass over S for it and q
    read = _dot(jnp.concatenate([b * grown * k, q * grown], 0), S)
    U = _forward_substitute(A, b * v - read[:C], solve)
    o = read[C:] + _dot(Mq, U)
    last = G[C - 1:]
    kend = k * jnp.exp(last - G)
    # kend^T and e^(G_C) as a column: one (d, d) transpose
    turned = jnp.where(
        _iota((d, 1), 0) == C, jnp.exp(last),
        jnp.concatenate([kend, jnp.zeros((d - C, d), _F32)], 0)).T
    S = turned[:, C:C + 1] * S + _dot(turned[:, :C], U)
    return o, S


def _kernel(q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, o_ref, out_ref, *,
            heads, d, sub, solve):
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        out_ref[...] = s_ref[...]

    # side by side in one block of code: the heads' chains of matmuls and
    # row steps fill each other's waits
    for h in range(heads):
        at = slice(h * d, (h + 1) * d)
        o, S = _one_head(q_ref[0, :, at], k_ref[0, :, at], v_ref[0, :, at],
                         g_ref[0, :, at], b_ref[0, 0, :, h:h + 1],
                         out_ref[0, h], sub=sub, solve=solve)
        o_ref[0, :, at] = o
        out_ref[0, h] = S


@functools.partial(jax.jit, static_argnames=("chunk", "hb", "sub", "solve",
                                             "interpret"))
def _call(q, k, v, g, b, state, *, chunk, hb, sub, solve, interpret):
    """The kernel over (N, T, H d) arrays, b (N, H / hb, T, hb): a head's
    column a lane.  Jitted, so that the layers of a program share one
    lowering of it."""
    N, T, wide = q.shape
    d = state.shape[-1]
    H = wide // d
    tile = pl.BlockSpec((1, chunk, hb * d), lambda n, hg, c: (n, c, hg))
    held = pl.BlockSpec((1, hb, d, d), lambda n, hg, c: (n, hg, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, heads=hb, d=d, sub=sub, solve=solve),
        grid=(N, H // hb, T // chunk),
        in_specs=[tile, tile, tile, tile,
                  pl.BlockSpec((1, 1, chunk, hb),
                               lambda n, hg, c: (n, hg, c, 0)),
                  held],
        out_specs=[tile, held],
        out_shape=[jax.ShapeDtypeStruct((N, T, H * d), _F32),
                   jax.ShapeDtypeStruct(state.shape, _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name="kda_chunk",
    )(q, k, v, g, b, state)


def kda_chunk(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
              b: jax.Array, state: jax.Array, chunk: int):
    """``models/kda.chunk_rule`` as one kernel.  q, k, v, g (N, T, H, d)
    and b (N, T, H) float32, T whole chunks, g and b 0 at padded positions;
    state (N, H, d, d) float32.  Returns (o (N, T, H, d) float32, the state
    after the last position); ``chunk_rule``'s own where the shape does
    not ``engage``."""
    N, T, H, d = q.shape
    if not engages(d, chunk):
        from ray_tpu.models.kda import chunk_rule

        return chunk_rule(q, k, v, g, b, state, chunk)
    hb = _heads_a_block(H)
    o, state = _call(
        *(x.reshape(N, T, H * d).astype(_F32) for x in (q, k, v, g)),
        jnp.moveaxis(b.astype(_F32).reshape(N, T, H // hb, hb), 2, 1),
        state.astype(_F32), chunk=chunk, hb=hb, sub=_SUB, solve=_SOLVE,
        interpret=_flash._use_interpret())
    return o.reshape(N, T, H, d), state

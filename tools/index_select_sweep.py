"""A prefill's selection of keys alone on the chip, at cell 10's geometry, as
one kernel and as XLA's form.

    chiprun -- python3 -m tools.index_select_sweep [rows,cols ...]

One row of 4,096 / 8,192 / 12,288 positions, 16 index heads of 64, ``topk``
2,048, tiles of 512 queries (``keye-vl-2.0-30b-a3b.serve-long-prompt``:
PERF.md section 4): bfloat16 ``qI`` and ``kI`` drawn normal, float32 ``w``
normal, as the projections of random weights give them.  Per bucket and
length (1.0, 0.8 and 0.6 of the bucket) the device ms a call of XLA's form
(``indexer.prefill_keep`` as it ran before the kernel: a ``lax.map`` over
tiles of ``scores`` + ``topk_keep``, not told the length) and of the kernel
(by its name, from a trace), the us a tile of 512 queries that runs, and
the kernel's time by STAGE -- scores alone (the MXU's K = 64 contraction,
the relu-weight-sum on the vector unit and the turn of the ordered bits into
bit planes), counting alone (the 32 passes of popcounts over a plane each,
and a row's decision a pass), the write alone (a shift, a mask and the int8
store a key), each a build of the kernel with the other stages left out
(``index_select.STAGES``; a stage's data does not change its time, and the
tie-break, which no stage alone takes, is in none) -- so that a vector-bound
tile can be told from a matmul-bound one: the MXU floor of a tile's scores
beside them, 2 x 512 x 16 x 64 x reach FLOPs at half the peak (K = 64 fills
half of the array's rows; ``benchmarks/lib/peaks.json``).  Then the kernel at
each ``ROWS,COLS`` given (default: what ships, ``*``).  Last, ON THE CHIP: the rows of the
kernel's mask that differ from XLA's at 4,096 positions (near-ties at the
2,048th place: the MXU's order inside a contraction is not the fusion's),
and that the selection stage alone is bit for bit ``topk_keep``'s on scores
both forms compute exactly (small whole numbers).  A tool: no cell runs it.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import runtime
from ray_tpu.models import indexer
from ray_tpu.ops import index_select as op
from tools.flash_sweep import _traced

BUCKETS = (4096, 8192, 12288)
HEADS, HEAD_DIM, TOPK = 16, 64, 2048
TILE = indexer.QUERY_TILE
REAL = (1.0, 0.8, 0.6)
CALLS = 3


def inputs(seed, P, whole=False):
    """``qI, kI_t, w`` of one row; ``whole``: small whole numbers, whose
    scores every order of summation computes exactly."""
    ks = jax.random.split(jax.random.key(seed), 3)
    if whole:
        draw = lambda key, shape, to: jax.random.randint(  # noqa: E731
            key, shape, -to, to + 1).astype(jnp.float32)
        return (draw(ks[0], (1, P, HEADS, HEAD_DIM), 3).astype(jnp.bfloat16),
                draw(ks[1], (1, HEAD_DIM, P), 2).astype(jnp.bfloat16),
                draw(ks[2], (1, P, HEADS), 2))
    return (jax.random.normal(ks[0], (1, P, HEADS, HEAD_DIM), jnp.bfloat16),
            jax.random.normal(ks[1], (1, HEAD_DIM, P), jnp.bfloat16),
            jax.random.normal(ks[2], (1, P, HEADS), jnp.float32))


def xla_form(qi, ki_t, w, lengths):
    """``indexer.prefill_keep`` with the kernel declined."""
    engages, op.engages = op.engages, lambda *a: False
    try:
        return indexer.prefill_keep(qi, ki_t, w, TOPK, lengths)
    finally:
        op.engages = engages


def kernel(qi, ki_t, w, lengths):
    return indexer.prefill_keep(qi, ki_t, w, TOPK, lengths)


def kernel_ms(args, stages=op.STAGES):
    shipped, op.STAGES = op.STAGES, stages
    try:
        # a jit of its own: one of ``kernel`` itself would hand back the
        # trace it made under the last ROWS, COLS and STAGES
        read = _traced(jax.jit(lambda *a: kernel(*a)), args, CALLS)
    finally:
        op.STAGES = shipped
    return 1e3 * read.seconds_matching("index_select_prefill") / CALLS


def mxu_floor_ms(P, length, peaks):
    """The tiles' score matmuls at half the MXU's peak, a block of ``ROWS``
    queries to its reach."""
    flops = 0
    for first in range(0, min(length, P), op.ROWS):
        reach = -(-(first + op.ROWS) // op.COLS) * op.COLS
        flops += 2 * op.ROWS * HEADS * HEAD_DIM * reach
    return 1e3 * flops / (0.5 * peaks["bf16_flops_per_s"])


def sweep(swept):
    peaks = runtime.load_peaks(jax.devices()[0].device_kind)
    print("form rows,cols bucket length ms us_a_tile scores_ms count_ms "
          "write_ms mxu_floor_ms")
    shipped = (op.ROWS, op.COLS)
    try:
        for P in BUCKETS:
            qi, ki_t, w = inputs(P, P)
            for real in REAL:
                length = int(real * P)
                args = (qi, ki_t, w, jnp.full((1,), length, jnp.int32))
                tiles = -(-length // TILE)
                if real == 1.0:
                    ms = 1e3 * _traced(jax.jit(xla_form), args,
                                       CALLS).busy_s / CALLS
                    print("xla -", P, "any", f"{ms:.3f}",
                          f"{1e3 * ms / (P // TILE):.1f}", flush=True)
                for rows, cols in swept:
                    op.ROWS, op.COLS = rows, cols
                    try:
                        ms = kernel_ms(args)
                        by_stage = [kernel_ms(args, (stage,))
                                    for stage in op.STAGES]
                    except Exception as e:      # what Mosaic will not lower
                        print("index_select", f"{rows},{cols}", P, length,
                              "refused:", str(e).splitlines()[0][:120],
                              flush=True)
                        continue
                    print("index_select", f"{rows},{cols}", P, length,
                          f"{ms:.3f}", f"{1e3 * ms / tiles:.1f}",
                          *(f"{s:.3f}" for s in by_stage),
                          f"{mxu_floor_ms(P, length, peaks):.3f}",
                          "*" * ((rows, cols) == shipped), flush=True)
    finally:
        op.ROWS, op.COLS = shipped
    P = BUCKETS[0]
    lengths = jnp.full((1,), P, jnp.int32)
    for name, args in (("drawn normal", inputs(7, P)),
                       ("whole numbers", inputs(7, P, whole=True))):
        want = np.asarray(jax.jit(xla_form)(*args, lengths))
        got = np.asarray(jax.jit(kernel)(*args, lengths))
        differ = (want != 0) != (got != 0)
        print(f"{name}: rows of {P} whose mask differs from XLA's:",
              int(differ.any(-1).sum()), "keys:", int(differ.sum()),
              "a row's most:", int(differ.sum(-1).max()),
              "keys a row kept (min, max):",
              int((got != 0).sum(-1).min()), int((got != 0).sum(-1).max()),
              flush=True)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        raise SystemExit(
            "index_select_sweep times the compiled kernel: tpu only")
    sweep(tuple(tuple(int(n) for n in a.split(","))
                for a in sys.argv[1:]) or ((op.ROWS, op.COLS),))

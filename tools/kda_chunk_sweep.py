"""The chunked delta rule alone on the chip, at cell 12's geometry, as one
kernel and as XLA's form.

    chiprun -- python3 -m tools.kda_chunk_sweep [heads-a-step ...]

One row of 4,096 / 8,192 / 12,288 positions, 64 heads of 128, chunks of 64
(``solar-open2-250b.serve-long-prompt``: PERF.md section 4), float32, the
decays drawn as the layer draws them (``A_log = log U[1, 16]`` a head, a
``dt`` log-uniform in [1e-3, 1e-1] a channel), ``b = 2 sigmoid(.)``; the
state walks blocks of 1,024 positions in a scan, as ``kda.prefill`` hands
them over.  Per prompt and form the device ms a 1,024-position block, read
from a trace (the kernel's by its name, ``chunk_rule``'s as all the device
ran), and the share of that time the work's two floors would take: a
(chunk, head)'s 12.6 MFLOP of float32 matmul at six bf16 passes against the
chip's bf16 peak, and q, k, v, g in and ``o`` out once against its HBM peak
(``benchmarks/lib/peaks.json``).  The kernel is timed at each number of
heads a grid step given (default 1 2 4 8); ``*`` marks what ``_HEADS``
ships.  Last, the largest gap of each form's ``o`` and state against the
float32 recurrence token by token ON THE CHIP over 4,096 positions: the
matrix unit's rounding, which interpret mode cannot show.  A tool: no cell
runs it.
"""

import sys

import jax
import jax.numpy as jnp

from benchmarks.lib import runtime
from ray_tpu.models import kda
from ray_tpu.ops import kda_chunk as op
from tools.flash_sweep import _traced

PROMPTS = (4096, 8192, 12288)
HEADS, D, CHUNK, BLOCK = 64, 128, 64, 1024
# a (chunk, head): the off-diagonal products 2.1, the solve as matmuls 2.1,
# Y S 2.1, q S 2.1, P U 1.05, K_end^T U 2.1, the running sum 1.05 MFLOP
CHUNK_HEAD_FLOPS, PASSES = 12.6e6, 6
SWEPT = (1, 2, 4, 8)


def inputs(seed, T):
    ks = jax.random.split(jax.random.key(seed), 8)
    shape = (1, T, HEADS, D)
    q = kda._l2norm(jax.random.normal(ks[0], shape)) * D ** -0.5
    k = kda._l2norm(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    a = jax.random.uniform(ks[3], (1, 1, HEADS, 1), minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(ks[4], (1, 1, HEADS, D),
                                    minval=jnp.log(1e-3),
                                    maxval=jnp.log(1e-1)))
    g = -a * jax.nn.softplus(
        jax.random.normal(ks[5], shape) + dt + jnp.log(-jnp.expm1(-dt)))
    b = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[6], shape[:3]))
    S = 0.3 * jax.random.normal(ks[7], (1, HEADS, D, D))
    return q, k, v, g, b, S


def by_blocks(form):
    """``form`` over a prompt as ``kda.prefill`` calls it: blocks of
    ``BLOCK`` positions in a scan, the state carried."""
    def run(q, k, v, g, b, S):
        def block(S, x):
            o, S = form(*x, S, CHUNK)
            return S, o

        cut = tuple(jnp.moveaxis(a.reshape((1, -1, BLOCK) + a.shape[2:]),
                                 1, 0) for a in (q, k, v, g, b))
        S, o = jax.lax.scan(block, S, cut)
        return jnp.moveaxis(o, 0, 1).reshape(q.shape), S

    return jax.jit(run)


def recurrence(q, k, v, g, b, S):
    def step(S, x):
        q, k, v, g, b = x
        S = jnp.exp(g)[..., None] * S
        r = jnp.einsum("nhk,nhkv->nhv", k, S, precision="highest")
        S = S + b[..., None, None] * k[..., None] * (v - r)[..., None, :]
        return S, jnp.einsum("nhk,nhkv->nhv", q, S, precision="highest")

    S, o = jax.lax.scan(
        step, S, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1), S


def sweep(swept):
    peaks = runtime.load_peaks(jax.devices()[0].device_kind)
    pairs = BLOCK // CHUNK * HEADS
    matmul_ms = 1e3 * pairs * CHUNK_HEAD_FLOPS * PASSES \
        / peaks["bf16_flops_per_s"]
    bytes_ms = 1e3 * 5 * BLOCK * HEADS * D * 4 / peaks["hbm_bytes_per_s"]
    print(f"floors a {BLOCK}-position block: matmul {matmul_ms:.3f} ms, "
          f"bytes {bytes_ms:.3f} ms")
    print("form heads_a_step T ms_a_block matmul_floor_share "
          "byte_floor_share")
    shipped = op._HEADS
    try:
        for T in PROMPTS:
            args = inputs(T, T)
            calls = 3
            ms = 1e3 * _traced(by_blocks(kda.chunk_rule), args,
                               calls).busy_s / calls / (T // BLOCK)
            print("chunk_rule -", T, f"{ms:.3f}", f"{matmul_ms / ms:.3f}",
                  f"{bytes_ms / ms:.3f}", flush=True)
            for heads in swept:
                op._HEADS = heads
                try:
                    ms = 1e3 * _traced(
                        by_blocks(op.kda_chunk), args,
                        calls).seconds_matching("kda_chunk") \
                        / calls / (T // BLOCK)
                except Exception as e:      # what Mosaic will not lower
                    print("kda_chunk", heads, T, "refused:",
                          str(e).splitlines()[0][:120], flush=True)
                    continue
                print("kda_chunk", heads, T, f"{ms:.3f}",
                      f"{matmul_ms / ms:.3f}", f"{bytes_ms / ms:.3f}",
                      "*" * (heads == shipped), flush=True)
    finally:
        op._HEADS = shipped
    args = inputs(7, PROMPTS[0])
    want = jax.jit(recurrence)(*args)
    print("largest gap against the float32 recurrence over",
          PROMPTS[0], "positions (o, state); the recurrence's largest "
          f"|o| {float(jnp.abs(want[0]).max()):.3f}, "
          f"|state| {float(jnp.abs(want[1]).max()):.3f}")
    for name, form in (("chunk_rule", kda.chunk_rule),
                       ("kda_chunk", op.kda_chunk)):
        got = by_blocks(form)(*args)
        print(name, *(f"{float(jnp.abs(a - w).max()):.3e}"
                      for a, w in zip(got, want)), flush=True)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        raise SystemExit("kda_chunk_sweep times the compiled kernel: tpu only")
    sweep(tuple(int(a) for a in sys.argv[1:]) or SWEPT)

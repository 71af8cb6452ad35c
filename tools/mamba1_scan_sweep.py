"""The Mamba-1 prefill recurrence alone on the chip, at cell 11's geometry,
as one kernel and as XLA's loop.

    chiprun -- python3 -m tools.mamba1_scan_sweep [positions-a-step ...]

One row of 4,096 / 8,192 / 12,288 positions, 5,120 channels of 16 state
dimensions (``phi-4-mini-flash-reasoning.serve-long-prompt``: PERF.md
section 4), float32, ``A`` and ``dt`` drawn as the layer draws them (``A_log
= log U[1, 16]``, a ``dt`` a softplus around a bias log-uniform in [1e-3,
1e-1]).  Per bucket and form the device ms a call and the us a position,
read from a trace (the kernel's by its name, the loop's as all the device
ran), the share of that time the bytes that must cross HBM would take at
the chip's peak (``benchmarks/lib/peaks.json``): ``u`` and ``dt`` in, ``y``
out, once; and ``busy_ms``, all the device ran in the call for either form
(the kernel's with XLA's pad, concatenate and slice around it), so that the
two forms can be read the same way.  The loop is
``mamba1.selective_scan`` 16 positions an iteration with the ``D u`` term
after it, as the cell ran it before the kernel.  The kernel is timed at each
number of positions a grid step given (default 64 128 256; 512 is 18 MB of
tiles, past the 16 MiB of scoped VMEM the call keeps to, and is refused;
``*`` marks what ``POSITIONS`` ships), on a row that fills its
bucket and, at the shipped block, on one that ends at 0.785 of it (the
cell's ``batch.prefill_padding_share`` is 21.5%: the blocks past a row's
length are not walked).  Last, the largest gap of the kernel's ``y`` and
state against the loop's ON THE CHIP over 4,096 positions: the chip's own
``exp`` on both sides, which interpret mode cannot show.  A tool: no cell
runs it.
"""

import sys

import jax
import jax.numpy as jnp

from benchmarks.lib import runtime
from ray_tpu.models import mamba1
from ray_tpu.ops import mamba1_scan as op
from tools.flash_sweep import _traced

BUCKETS = (4096, 8192, 12288)
CHANNELS, STATE, CHUNK = 5120, 16, 16
SWEPT = (64, 128, 256)
REAL = 0.785


def inputs(seed, P, length):
    ks = jax.random.split(jax.random.key(seed), 7)
    A = -jax.random.uniform(ks[0], (STATE, CHANNELS), minval=1.0,
                            maxval=16.0)
    rate = jnp.exp(jax.random.uniform(
        ks[1], (CHANNELS,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (1, P, CHANNELS))
                         + rate + jnp.log(-jnp.expm1(-rate)))
    dt = jnp.where(jnp.arange(P)[None, :, None] < length, dt, 0.0)
    u = jax.nn.silu(2.0 * jax.random.normal(ks[3], (1, P, CHANNELS)))
    B, C = (jax.random.normal(k, (1, P, STATE)) for k in ks[4:6])
    D = 1.0 + 0.1 * jax.random.normal(ks[6], (CHANNELS,))
    return u, dt, A, B, C, D, jnp.full((1,), length, jnp.int32)


@jax.jit
def loop(u, dt, A, B, C, D, lengths):
    y, S = mamba1.selective_scan(u, dt, A, B, C, CHUNK)
    return y + D * u, S


def kernel(u, dt, A, B, C, D, lengths):
    return op.mamba1_scan(u, dt, A, B, C, D, lengths, CHUNK)


def sweep(swept):
    peaks = runtime.load_peaks(jax.devices()[0].device_kind)
    print("form positions_a_step bucket length ms us_a_position "
          "byte_floor_share busy_ms")
    shipped = op.POSITIONS
    calls = 3

    def line(form, block, P, length, seconds, busy, mark=""):
        # ms: the loop's every device-busy second, the kernel's by its
        # name; busy_ms: every device-busy second of either form's call
        # (the kernel's with XLA's pad, concatenate and slice around it)
        ms = 1e3 * seconds / calls
        floor_ms = 1e3 * 3 * P * CHANNELS * 4 / peaks["hbm_bytes_per_s"]
        print(form, block, P, length, f"{ms:.3f}", f"{1e3 * ms / P:.4f}",
              f"{floor_ms / ms:.3f}", f"{1e3 * busy / calls:.3f}", mark,
              flush=True)

    try:
        for P in BUCKETS:
            busy = _traced(loop, inputs(P, P, P), calls).busy_s
            line("loop", "-", P, P, busy, busy)
            for block in swept:
                op.POSITIONS = block
                for length in (P,) + ((int(REAL * P),)
                                      if block == shipped else ()):
                    args = inputs(P, P, length)
                    try:
                        read = _traced(jax.jit(kernel), args, calls)
                        seconds = read.seconds_matching("mamba1_scan")
                    except Exception as e:      # what Mosaic will not lower
                        print("mamba1_scan", block, P, length, "refused:",
                              str(e).splitlines()[0][:120], flush=True)
                        continue
                    line("mamba1_scan", block, P, length, seconds,
                         read.busy_s, "*" * (block == shipped))
    finally:
        op.POSITIONS = shipped
    P = BUCKETS[0]
    real = P - P // 40
    args = inputs(7, P, real)
    want = loop(*args)
    got = jax.jit(kernel)(*args)
    print("largest gap against the loop over", real, "real positions of", P,
          "(y, state); the loop's largest "
          f"|y| {float(jnp.abs(want[0][:, :real]).max()):.3f}, "
          f"|state| {float(jnp.abs(want[1]).max()):.3f}")
    print("mamba1_scan",
          f"{float(jnp.abs(got[0] - want[0])[:, :real].max()):.3e}",
          f"{float(jnp.abs(got[1] - want[1]).max()):.3e}",
          "y past the last group walked all zero:",
          not bool(got[0][:, -(-real // 8) * 8:].any()), flush=True)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        raise SystemExit(
            "mamba1_scan_sweep times the compiled kernel: tpu only")
    sweep(tuple(int(a) for a in sys.argv[1:]) or SWEPT)

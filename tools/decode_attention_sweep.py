"""The decode attention kernel alone on the chip, at each serve cell's
geometry, over several blocks.

    chiprun -- python3 -m tools.decode_attention_sweep [cell ...]

Per cell that runs ``ops/decode_attention.py`` (PERF.md section 4) one
pool of the cell's ``(B, Hq, Hkv, D, S)`` in bf16, stored as rows, the
rows as long as the cell's traffic leaves them (the quantiles of its
prompt lengths plus half an output; a ring's at ``min(length, ring)``);
per block the device time a call, read from a trace by the kernel's name
with the benchmark's own reader; beside it the blocks the call walks, the
us a block, and the GB/s of the live rows' K and V (what the rooflines
price) against the chip's peak (``benchmarks/lib/peaks.json``).  ``err``
is the largest difference from XLA's attention
(``_xla_decode_attention``) over the first eight rows.  The line marked
``*`` is what ``block_k`` chooses (PERF.md section 6, PR 52).  A tool: no
cell runs it.
"""

import statistics
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import runtime, swa_names
from ray_tpu.ops import decode_attention as da
from tools.flash_sweep import _traced

# cell: B, Hq, Hkv, D, S, layers of the pool, the rows' (median, sigma,
# least, most) positions, keys a row keeps (a learned selection) or None
LONG = (6144 + 128, 0.45, 1024, 12288 + 384)
CELLS = {
    "3": (120, 16, 8, 128, 512, 24, (192, 0.6, 32, 511), None),
    "4": (40, 16, 8, 128, 1280, 24, (600, 0.5, 64, 1279), None),
    "5": (120, 16, 16, 128, 512, 8, (192, 0.6, 32, 511), None),
    # GQA 32/8 x 64 kept two heads a 128-lane row: what the kernel sees
    "6": (80, 32, 4, 128, 512, 4, (192, 0.6, 32, 511), None),
    "7.full": (48, 28, 4, 128, 16384, 2, LONG, None),
    "7.ring": (48, 28, 4, 128, 4096, 6, LONG, None),
    "9": (240, 32, 4, 128, 512, 3, (192, 0.6, 32, 511), None),
    "10": (16, 32, 4, 128, 16384, 6, LONG, 2048),
    "11.pool": (64, 40, 10, 128, 16384, 1, LONG, None),
    "11.ring": (64, 40, 10, 128, 512, 8, LONG, None),
}
BLOCKS = (64, 128, 256, 512)


def lengths(B, median, sigma, least, most):
    """``B`` quantiles of the cell's lognormal row lengths."""
    z = np.asarray([statistics.NormalDist().inv_cdf((i + 0.5) / B)
                    for i in range(B)])
    return np.clip(median * np.exp(sigma * z), least, most).astype(np.int32)


def device_ms(fn, args, calls=10):
    """Device ms a call of the kernel inside ``fn``, by the name the
    benchmark's readers find it by."""
    return 1e3 * _traced(fn, args, calls).seconds_matching(
        swa_names.DECODE_ATTENTION_KERNEL) / calls


def sweep(cells):
    chosen = da.block_k
    try:
        _sweep(cells, chosen)
    finally:
        da.block_k = chosen


def _sweep(cells, chosen):
    peak = runtime.load_peaks(
        jax.devices()[0].device_kind)["hbm_bytes_per_s"]
    print("cell B Hq Hkv D S block blocks ms us/block GB/s of_peak err")
    for cell in cells:
        B, hq, hkv, d, S, L, rows, topk = CELLS[cell]
        lens = jnp.asarray(np.minimum(lengths(B, *rows), S - 1))
        active = jnp.ones(B, bool)
        keys = jax.random.split(jax.random.key(S + hq), 3)
        q = jax.random.normal(keys[0], (B, hq, d), jnp.bfloat16)
        ck, cv = (jnp.tile(jax.random.normal(k, (L, 1, S * hkv, d),
                                             jnp.bfloat16), (1, B, 1, 1))
                  for k in keys[1:])
        keep = None
        if topk:                 # a selection's size: the most recent
            at = jnp.arange(S)[None, :]
            keep = (at <= lens[:, None]) & (at > lens[:, None] - topk)
        layer = jnp.int32(L - 1)
        n = np.asarray(lens) + 1
        want = da._xla_decode_attention(
            q[:8], *(c[:, :8].reshape(L, 8, S, hkv, d) for c in (ck, cv)),
            layer, jnp.asarray(n[:8]), S, d ** -0.5,
            None if keep is None else keep[:8])
        shipped = chosen(S, hkv, d, 2)
        for block in sorted({min(block, S) for block in BLOCKS}):
            da.block_k = lambda *_, block=block: block
            fn = jax.jit(lambda q, ck, cv, lens, keep: da.decode_attention(
                q, ck, cv, layer, lens, active, s_active=S,
                scale=d ** -0.5, hkv=hkv, keep=keep))
            try:
                ms = device_ms(fn, (q, ck, cv, lens, keep))
            except Exception as e:      # what Mosaic will not lower
                print(cell, B, hq, hkv, d, S, block, "refused:",
                      str(e).splitlines()[0][:120], flush=True)
                continue
            err = float(jnp.abs(
                fn(q, ck, cv, lens, keep)[:8].astype(jnp.float32)
                - want.astype(jnp.float32)).max())
            walked = int(np.ceil(n / block).sum())
            rate = float(n.sum()) * hkv * d * 2 * 2 / (ms / 1e3 or 1)
            print(cell, B, hq, hkv, d, S, block, walked,
                  f"{ms:.3f}", f"{1e3 * ms / walked:.3f}",
                  f"{rate / 1e9:.1f}", f"{rate / peak:.3f}",
                  f"{err:.4f}", "*" * (block == shipped), flush=True)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        raise SystemExit(
            "decode_attention_sweep times the compiled kernel: tpu only")
    sweep(sys.argv[1:] or list(CELLS))

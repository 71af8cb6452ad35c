"""The three flash kernels alone on the chip, over tile and strip widths.

    chiprun -- python3 -m tools.flash_sweep

Both train cells' shapes (PERF.md section 4), causal, bf16; per (block,
strip) the device time a call of forward, dq and dk/dv, read from a trace
by the kernels' names with the benchmark's own reader, beside each the MB
the call moves between HBM and VMEM by its own BlockSpecs
(``blockspec_bytes``) and, last, the GB/s of the three together and the
score elements they compute a second.  ``strip`` is dq's and dk/dv's;
``strip == block`` is a diagonal tile computed whole and masked by a
select, as the forward always computes it.  What it read last stands over
``DEFAULT_BLOCK`` in ``ray_tpu/ops/flash_attention.py``.

    chiprun -- python3 -m tools.flash_sweep grad

alone times what a model's layer runs, the forward and backward of the
public ``flash_attention`` (the model's ``(B, S, H, D)`` layout, shipped
tiles) in one jitted program: the device ms a call of the whole program,
of the three kernels in it, and the difference — XLA's side of attention,
the transposes, ``delta`` and whatever copies the kernels' operands and
results need, which computes nothing.  It touches the public function
alone, so this file copied into an older checkout reads that one the same
way.
With no argument both tables are printed, this one first.

    chiprun -- python3 -m tools.flash_sweep prefill

alone times the forward as SERVING calls it (``flash_prefill_attention``,
one row in a bucket) at the three long-prompt cells' shapes: cell 8's
expanded latent head group (32 heads, q/k 192, v 128, no ``lse``), cell
7's window and full layers (28 / 4 x 128, a band of 4,096 or none, with
``lse``) and cell 10's masked call (32 / 4 x 128, ``keep`` with the 2,048
most recent keys, no ``lse``); per bucket a few prompt lengths, the device
ms a call without ``lengths`` (every causal tile of the bucket) and with
them (q blocks wholly past the prompt's end declined), and beside them the
share of the bucket's causal tiles that ``causal_computed_share`` says the
told kernel computes: the ms should follow it.
"""

import importlib
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import jaxpr_as_fun

from benchmarks.lib import flash_names, trace_reduce

fa = importlib.import_module("ray_tpu.ops.flash_attention")
SHAPES = [(8, 2048, 15, 5, 64), (1, 4096, 16, 8, 128)]  # B, S, Hq, Hkv, D
KERNELS = tuple(flash_names.KERNEL_OPS)                  # fwd, dq, dkdv


def _traced(fn, args, calls):
    """The device trace of ``calls`` calls of ``fn``, warmed first."""
    jax.block_until_ready(fn(*args))            # compile, warm
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready([fn(*args) for _ in range(calls)])
        return trace_reduce.read(trace_dir)


def kernel_ms(fn, args, calls=10):
    """Device ms a call of each kernel inside ``fn`` and, under
    ``"whole"``, of all of ``fn`` (the time an op ran), from a trace."""
    trace = _traced(fn, args, calls)
    ms = {kernel: 1e3 * flash_names.kernel_seconds(trace, kernel) / calls
          for kernel in KERNELS}
    return {**ms, "whole": 1e3 * trace.busy_s / calls}


def blockspec_bytes(fn, *args):
    """Bytes every ``pallas_call`` directly inside ``fn`` moves between
    HBM and VMEM, by its name: each operand's and result's block, its
    minor dimension padded to whole lanes as the tiled layout stores it,
    once for every step of the grid at which its index map names another
    block than at the step before (what the pipeline copies)."""
    moved = {}
    for eqn in jax.make_jaxpr(fn)(*args).jaxpr.eqns:
        if eqn.primitive.name != "pallas_call":
            continue
        mapping = eqn.params["grid_mapping"]
        steps = np.indices(mapping.grid).reshape(len(mapping.grid), -1)
        total = 0
        for block in mapping.block_mappings:
            index_map = jaxpr_as_fun(block.index_map_jaxpr)
            index = np.stack(jax.vmap(index_map)(*steps), axis=1)
            copies = 1 + np.count_nonzero((index[1:] != index[:-1]).any(1))
            *outer, lanes = block.block_aval.shape
            lanes += -lanes % fa.LANES
            total += (copies * int(np.prod(outer)) * lanes
                      * block.array_aval.dtype.itemsize)
        moved[eqn.params["name"]] = total
    return moved


def _inputs(seed, q_shape, kv_shape):
    """bf16 ``q, k, v, do`` (``do`` shaped as ``q``) from ``seed``."""
    keys = jax.random.split(jax.random.key(seed), 4)
    q, do = (jax.random.normal(k, q_shape, jnp.bfloat16) for k in keys[:2])
    k, v = (jax.random.normal(k, kv_shape, jnp.bfloat16) for k in keys[2:])
    return q, k, v, do


def grad_sweep():
    """``jit`` of ``flash_attention``'s forward and vjp as a layer calls
    them: whole, the three kernels, and XLA's side (the difference)."""
    print("B S Hq Hkv D whole_ms", *(f"{kernel}_ms" for kernel in KERNELS),
          "xla_side_ms")

    def layer(q, k, v, do):
        o, vjp = jax.vjp(fa.flash_attention, q, k, v)
        return o, vjp(do)

    for B, S, Hq, Hkv, D in SHAPES:
        ms = kernel_ms(jax.jit(layer),
                       _inputs(S, (B, S, Hq, D), (B, S, Hkv, D)))
        kernels = sum(ms[kernel] for kernel in KERNELS)
        print(B, S, Hq, Hkv, D, f"{ms['whole']:.3f}",
              *(f"{ms[kernel]:.3f}" for kernel in KERNELS),
              f"{ms['whole'] - kernels:.3f}", flush=True)


def sweep(blocks=(512, 1024), strips=(128, 256, 512, None)):
    shipped = fa.DIAG_STRIP
    try:
        _sweep(blocks, strips)
    finally:
        fa.DIAG_STRIP = shipped


def _sweep(blocks, strips):
    print("B S Hq Hkv D block strip", *(f"{kernel}_ms {kernel}_MB"
                                        for kernel in KERNELS),
          "GB/s Gelem/s")
    for B, S, Hq, Hkv, D in SHAPES:
        q, k, v, do = _inputs(S, (B, Hq, S, D), (B, Hkv, S, D))
        for block in blocks:
            for strip in dict.fromkeys(s or block for s in strips):
                fa.DIAG_STRIP = strip           # read when a kernel is traced
                kw = dict(causal=True, block_q=block, block_k=block,
                          interpret=fa._use_interpret())

                def three(q, k, v, do):
                    o, lse = fa._fwd(q, k, v, **kw)
                    return o, fa._bwd_impl(q, k, v, o, lse, do, **kw,
                                           out_dtype=q.dtype)

                ms = kernel_ms(jax.jit(three), (q, k, v, do))
                by_name = blockspec_bytes(three, q, k, v, do)
                moved = {kernel: by_name[f"flash_attention_{kernel}"]
                         for kernel in KERNELS}
                share = fa.causal_computed_share    # the forward: no strips
                elems = B * Hq * S * S * (share(S, block, block, block)
                                          + 2 * share(S, block, block, strip))
                seconds = sum(ms[kernel] for kernel in KERNELS) / 1e3 or 1
                print(B, S, Hq, Hkv, D, block, strip,
                      *(f"{ms[kernel]:.3f} {moved[kernel] / 1e6:.1f}"
                        for kernel in KERNELS),
                      f"{sum(moved.values()) / seconds / 1e9:.1f}",
                      f"{elems / seconds / 1e9:.1f}", flush=True)


PREFILL_KERNEL = r"^%(flash|sparse)_prefill_attention(\.\w+)* = "
# cell, Hq, Hkv, D, Dv, flash_prefill_attention's options
PREFILL_SHAPES = [
    ("8", 32, 32, 192, 128, dict(lse=False)),
    ("7.window", 28, 4, 128, 128, dict(window=4096)),
    ("7.full", 28, 4, 128, 128, dict()),
    ("10", 32, 4, 128, 128, dict(lse=False, keep=2048)),
]
# bucket -> prompt lengths: just past the bucket below, the traffic's
# middle of the bucket, the bucket's end
PREFILL_LENGTHS = {4096: (2100, 3300, 4096), 8192: (4200, 6144, 8192),
                   12288: (8300, 10000, 12288)}


def prefill_ms(fn, args, calls=5):
    trace = _traced(fn, args, calls)
    return 1e3 * trace.seconds_matching(PREFILL_KERNEL) / calls


def prefill_sweep():
    print("cell Hq Hkv D Dv bucket length untold_ms told_ms told/untold "
          "tiles_told/untold")
    share = fa.causal_computed_share
    for cell, Hq, Hkv, D, Dv, options in PREFILL_SHAPES:
        for S, lengths in PREFILL_LENGTHS.items():
            q, k, v, _ = _inputs(S, (1, S, Hq, D), (1, S, Hkv, D))
            v = v[..., :Dv]
            kw = dict(options, scale=D ** -0.5)
            if "keep" in kw:        # a selection's size: the most recent
                at = jnp.arange(S)
                behind = at[:, None] - at[None, :]
                kw["keep"] = ((behind >= 0) & (behind < kw["keep"])
                              ).astype(jnp.int8)[None]
            untold = prefill_ms(jax.jit(
                lambda q, k, v: fa.flash_prefill_attention(q, k, v, **kw)),
                (q, k, v))
            told = jax.jit(lambda q, k, v, n: fa.flash_prefill_attention(
                q, k, v, lengths=n, **kw))
            for n in lengths:
                ms = prefill_ms(told, (q, k, v, jnp.asarray([n], jnp.int32)))
                block = fa._block_sizes(S, S, None, None)[0]
                tiles = (share(S, strip=block, length=n)
                         / share(S, strip=block))
                print(cell, Hq, Hkv, D, Dv, S, n, f"{untold:.3f}",
                      f"{ms:.3f}", f"{ms / untold:.3f}", f"{tiles:.3f}",
                      flush=True)


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        raise SystemExit("flash_sweep times the compiled kernels: tpu only")
    if sys.argv[1:] == ["prefill"]:
        prefill_sweep()
        raise SystemExit
    grad_sweep()
    if sys.argv[1:] != ["grad"]:
        sweep()

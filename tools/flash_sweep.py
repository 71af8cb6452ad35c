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


def kernel_ms(fn, args, calls=10):
    """Device ms a call of each kernel inside ``fn`` and, under
    ``"whole"``, of all of ``fn`` (the time an op ran), from a trace."""
    jax.block_until_ready(fn(*args))            # compile, warm
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            jax.block_until_ready([fn(*args) for _ in range(calls)])
        trace = trace_reduce.read(trace_dir)
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


if __name__ == "__main__":
    if jax.default_backend() != "tpu":
        raise SystemExit("flash_sweep times the compiled kernels: tpu only")
    grad_sweep()
    if sys.argv[1:] != ["grad"]:
        sweep()

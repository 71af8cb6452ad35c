"""A serve cell's warmed prefill programs alone on the chip.

    chiprun -- python3 -m tools.prefill_times internlm2-1.8b.serve-batch-decode

Builds the engine's ``prefill`` program from the cell's configuration and
engine block (``benchmarks/workloads/<cell>.json``: slots, positions,
buckets) with weights drawn on the device, and times rows x bucket for
every rung of ``--rows`` and every bucket, one line each: host clock
around ``--calls`` launches that hand the donated cache on, after a
warm-up call of each shape.  No scheduler, no decode beside it: what a
launch costs whatever its size and what a position costs, as PERF.md
section 6 (PR 27, PR 33) and the comments over ``serve/llm.py``'s
``_LAUNCH_POSITIONS`` read them.  A time is the chip's or it is none:
anywhere but on a TPU the tool exits before it builds anything.
"""

import argparse
import time

import jax
import jax.numpy as jnp

from benchmarks.lib import program, spec
from ray_tpu.models import llama, llama_serve


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("cell")
    parser.add_argument("--rows", type=int, nargs="+", default=[1, 2, 4, 8])
    parser.add_argument("--calls", type=int, default=20)
    args = parser.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("prefill_times times the compiled programs: "
                         "tpu only")

    cell = spec.Cell(args.cell)
    engine = cell.workload["engine"]
    slots, max_len = engine["max_slots"], engine["max_len"]
    cfg = program.llama_config(cell.config, max_seq_len=max_len)
    params = jax.jit(lambda k: llama.init_params(k, cfg, cfg.dtype))(
        jax.random.key(0))
    cache = llama_serve.init_cache(cfg, slots, max_len)
    prefill = llama_serve.build_prefill(cfg)
    device = jax.devices()[0]
    print(f"{args.cell}: {slots} slots x {max_len} on {device.platform} "
          f"{device.device_kind}", flush=True)
    print("rows bucket ms_a_launch us_a_position", flush=True)
    for bucket in engine["prefill_buckets"]:
        for rows in args.rows:
            tokens = jnp.ones((rows, bucket), jnp.int32)
            lengths = jnp.full((rows,), bucket, jnp.int32)
            group = jnp.arange(rows, dtype=jnp.int32)
            cache, first, _load = prefill(params, cache, tokens, lengths,
                                          group)           # compile, warm
            jax.block_until_ready(first)
            start = time.perf_counter()
            for _ in range(args.calls):
                cache, first, _load = prefill(params, cache, tokens,
                                              lengths, group)
            jax.block_until_ready((cache, first))
            ms = 1e3 * (time.perf_counter() - start) / args.calls
            print(f"{rows} {bucket} {ms:.3f} "
                  f"{1e3 * ms / (rows * bucket):.2f}", flush=True)


if __name__ == "__main__":
    main()

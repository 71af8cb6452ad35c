"""Compile a train cell's step at its REAL widths for a v5e that is
described, not attached (no chip time; a minute or more a compile, so by
hand and not among the tests):

    JAX_PLATFORMS=cpu python3 benchmarks/tools/train_step_aot.py \\
        --config trinity-mini --traffic train-8k-1chip [--batch 2]

Prints the compiler's verdict (a step that does not fit the chip's HBM is
refused with RESOURCE_EXHAUSTED and the compiler's own account), its memory
analysis, and the step's Mosaic kernels by name and by the scope the
readers sum them under.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks.lib import program  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--batch", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmarks.tests.test_aot_real_widths import (
        kernels_by_name_and_scope)
    from ray_tpu.models import llama

    # the backend here is the CPU, the target the chip
    importlib.import_module(
        "ray_tpu.ops.flash_attention")._use_interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)

    def load(kind, name):
        with open(os.path.join(BENCH_DIR, kind, name + ".json")) as f:
            return json.load(f)

    cfg = program.llama_config(load("configs", args.config))
    traffic = load("traffic", args.traffic)
    shape = (args.batch or traffic["batch"], traffic["seq_len"])
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        jax.eval_shape(
            llama._train_state_builder(cfg, None, True, None, None),
            jax.random.key(0)))
    batch = {"tokens": jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)}
    t0 = time.perf_counter()
    try:
        compiled = llama.make_train_step(cfg, fused=True).lower(
            state, batch).compile()
    except jax.errors.JaxRuntimeError as e:
        print(json.dumps({"shape": shape, "refused": str(e)[:1500]}))
        return 1
    memory = compiled.memory_analysis()
    print(json.dumps({
        "shape": shape, "compile_s": round(time.perf_counter() - t0, 1),
        "argument_bytes": memory.argument_size_in_bytes,
        "aliased_bytes": memory.alias_size_in_bytes,
        "temp_bytes": memory.temp_size_in_bytes,
        "kernels": {f"{kernel} under {scope}": n for (kernel, scope), n in
                    sorted(kernels_by_name_and_scope(
                        compiled.as_text()).items())}}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

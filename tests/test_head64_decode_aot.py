"""Cells 9's and 6's ``decode_k`` at their real slots, compiled for a v5e
that is described, not attached (as ``tests/test_flash_stats_aot.py``
compiles): K and V of heads of 64 lie two a 128-lane row, so every
attention layer is ONE ``decode_attention`` Mosaic call, traced under the
step's scope ``attention``, over the pool where it lies -- nothing of
a layer's ``(1, slots, 512, 8, 64)`` bucket is sliced out of it, and lfm2's
step carries no second copy of its pool as scratch.  Nothing runs, so
nothing here is a speed.

The topology is described inside a fixture and the compiles run in the
test's own process: the TPU library loads once, in the worker that gets
this file.
"""

import importlib
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

# cell: attention layers, scratch of ``decode_k`` at most (bytes; lfm2's was
# 1.58 GB with XLA's staging copies, AOT, PR 40)
CELLS = {
    "lfm2-8b-a1b.serve-batch-decode-wide": (3, 0.5e9),
    "granite-4.0-h-micro.serve-batch-decode": (4, 1.0e9),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def compiled_for_the_chip(monkeypatch):
    """The backend here is the CPU but the target is the chip: the kernels
    are steered to Mosaic, and the compiles kept out of the persistent
    cache, which cannot read them back without a chip."""
    import jax

    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "_use_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_decode_k_attends_the_pool_where_it_lies(
        topo, compiled_for_the_chip, cell):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmarks.lib import program
    from benchmarks.tests.test_aot_real_widths import (
        MOSAIC, _json, _on, kernels_by_name_and_scope)
    from ray_tpu.models import llama, llama_serve

    layers, scratch = CELLS[cell]
    work = _json("workloads", cell)
    slots, max_len = work["engine"]["max_slots"], work["engine"]["max_len"]
    cfg = program.llama_config(_json("configs", work["config"]),
                               max_seq_len=max_len)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.kv_row_heads, cfg.kv_row_dim) == (4, 128)
    one_chip = SingleDeviceSharding(topo.devices[0])
    params = _on(one_chip, jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: llama_serve.init_cache(cfg, slots, max_len)))
    assert cache["k"].shape == cache["v"].shape == (
        layers, slots, max_len * 4, 128)
    ints = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=one_chip)
    bools = jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=one_chip)
    compiled = llama_serve.build_decode_k(cfg).lower(
        params, cache, ints, ints, ints, ints, bools, bools, k=16,
        s_active=max_len).compile()
    text = compiled.as_text()
    # one call a layer of the scanned period (lfm2: one attention layer a
    # period of four; granite: one of ten), traced under the step's scope
    # ``attention``; the kernel keeps its own scope inside it, as in every
    # other cell (``device.scope_of``: the innermost word), and what is
    # left under ``attention`` is the widening of the queries and the
    # halves kept of the result
    kernels = kernels_by_name_and_scope(text)
    assert kernels["decode_attention", "decode_attention"] >= 1
    call, = [line for line in text.splitlines()
             if MOSAIC in line and "%decode_attention" in line.split(" = ")[0]]
    assert "/attention/decode_attention/" in call
    assert f"bf16[{layers},{slots},{max_len * 4},128]" in call   # the pool
    from ray_tpu.observability import device
    assert "attention" in {scope for scope, _ in
                           device.scopes_of_text(text).values()}
    # no bucket of every slot copied out of the pool, in either layout
    by_position = rf"bf16\[1,{slots},{max_len},8,64\]"
    as_rows = rf"bf16\[1,{slots},{max_len * 4},128\]"
    assert not [line for line in text.splitlines()
                if "dynamic-slice" in line.split(" = ")[0]
                and re.search(f"{by_position}|{as_rows}", line)]
    assert not re.search(by_position, text)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < scratch
    # the pool is the step's own carry: updated in place
    pool = 2 * layers * slots * max_len * 8 * 64 * 2
    assert memory.alias_size_in_bytes >= pool

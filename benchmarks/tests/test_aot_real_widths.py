"""Compile the cells' programs at their REAL sizes for a v5e that is
described, not attached: what the chip's compiler refuses (a kernel it
cannot tile, a program over 16 GB) costs no chip time here.  This is
what sized the batch of the train cells and the slots of the serve
cells (PERF.md section 4).  Nothing runs, so nothing here is a speed.

All in this one file, the topology described inside a fixture, compiles
in the test's own process: the TPU library loads once, in the worker
that gets the file.
"""

import collections
import json
import os
import re

import pytest

from benchmarks.lib import program, spec

os.environ.setdefault("TPU_LOG_DIR", "disabled")
MOSAIC = 'custom_call_target="tpu_custom_call"'


def _json(kind, name):
    with open(os.path.join(spec.BENCH_DIR, kind, name + ".json")) as f:
        return json.load(f)


def kernels_by_name_and_scope(compiled_text):
    """``{(kernel, scope): calls}`` of a compiled module's Mosaic kernels:
    the instruction's name without its dotted suffix (``decode_attention``,
    ``ragged-dot-none``: what a reader that goes by name matches in a
    trace) and the scope the program's own map gives the instruction (what
    a reader that goes by scope sums).  A cell's test holds the kernels
    its readers name, each under the scope they expect, and not the
    module's total: a kernel more is no reader's loss."""
    from ray_tpu.observability import device

    scopes = device.scopes_of_text(compiled_text)
    found = collections.Counter()
    for line in compiled_text.splitlines():
        if MOSAIC not in line:
            continue
        text = line.strip()
        text = text[len("ROOT "):] if text.startswith("ROOT ") else text
        kernel = text.split(" = ", 1)[0].lstrip("%").split(".", 1)[0]
        scope = scopes.get(device.instruction_key(text), ("?", ""))[0]
        found[kernel, scope] += 1
    return found


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def compiled_kernels(monkeypatch):
    """``flash_attention`` asks ``jax.default_backend()`` whether to
    interpret its kernels; here the backend is the CPU but the target is
    the chip, so the test steers it — and keeps the compiles out of the
    persistent cache, which cannot read them back without a chip."""
    import importlib

    import jax

    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "_use_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)


def _on(sharding, tree):
    import jax

    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sharding), tree)


# ------------------------------------------------------------ the kernels
@pytest.mark.parametrize("batch,seq,heads,kv_heads,head_dim", [
    (8, 2048, 15, 5, 64),     # smollm2-360m.train-1chip
    (1, 4096, 16, 8, 128),    # internlm2-1.8b.train-fsdp4, one chip's share
])
def test_flash_forward_and_backward_compile(one_chip, batch, seq, heads,
                                            kv_heads, head_dim):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    q = jax.ShapeDtypeStruct((batch, seq, heads, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((batch, seq, kv_heads, head_dim),
                              jnp.bfloat16, sharding=one_chip)
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    assert hlo.count(MOSAIC) == 3      # forward, dq, dk/dv


# -------------------------------------------------------- the train steps
def _train_step(config_name, traffic_name, mesh, devices, batch=None):
    """The compiled train step of a cell (or of the cell at another
    ``batch``): state and batch as shapes under the shardings the program
    itself gives them."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, use_mesh
    from ray_tpu.parallel.sharding import (current_mesh, current_rules,
                                           logical_sharding)

    cfg = program.llama_config(_json("configs", config_name))
    traffic = _json("traffic", traffic_name)
    shape = (batch or traffic["batch"], traffic["seq_len"])
    step = llama.make_train_step(cfg, fused=True)
    if mesh is None:
        from jax.sharding import SingleDeviceSharding

        one = SingleDeviceSharding(devices[0])
        state = _on(one, jax.eval_shape(
            llama._train_state_builder(cfg, None, True, None, None),
            jax.random.key(0)))
        batch = {"tokens": jax.ShapeDtypeStruct(shape, jnp.int32,
                                                sharding=one)}
        return step.lower(state, batch).compile()
    with use_mesh(MeshSpec(**mesh).build(devices)):
        state = llama._train_state_builder(
            cfg, None, True, current_mesh(), current_rules()
        ).eval_shape(jax.random.key(0))
        batch = {"tokens": jax.ShapeDtypeStruct(
            shape, jnp.int32, sharding=logical_sharding(
                ("batch", None), current_mesh(), current_rules()))}
        return step.lower(state, batch).compile()


def test_smollm2_step_fits_one_chip(topo):
    """8 x 2048 compiles for one 16 GB chip with the three Mosaic calls
    in it (12 x 2048 is refused: 18.95 GB of 15.75 — PERF.md)."""
    compiled = _train_step("smollm2-360m", "train-1chip", None,
                           topo.devices)
    assert compiled.as_text().count(MOSAIC) == 3
    state_bytes = compiled.memory_analysis().argument_size_in_bytes
    assert 4.3e9 < state_bytes < 4.5e9       # 12 bytes a parameter


def test_internlm2_step_fits_four_chips(topo):
    """4 x 4096 under fsdp=4: compiles within each chip's 16 GB, holds a
    quarter of the 22.7 GB state a chip, the per-shard Mosaic calls and
    the collectives that gather the sharded parameters."""
    compiled = _train_step("internlm2-1.8b", "train-fsdp4",
                           _json("workloads", "internlm2-1.8b.train-fsdp4")
                           ["trainer"]["mesh"], topo.devices)
    hlo = compiled.as_text()
    assert hlo.count(MOSAIC) == 3
    assert len(re.findall(r"\ball-gather(?:-start)?\(", hlo)) >= 24
    assert len(re.findall(r"\b(?:all-reduce|reduce-scatter)"
                          r"(?:-start)?\(", hlo)) >= 1
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert 5.6e9 < per_chip < 5.8e9          # 22.7 GB / 4


@pytest.mark.parametrize("config,traffic,mesh,batch,used", [
    ("smollm2-360m", "train-1chip", None, 12, "18.95G of 15.75G"),
    ("internlm2-1.8b", "train-fsdp4", {"fsdp": 4}, 12, "19.03G of 15.75G"),
])
def test_a_larger_batch_is_refused(topo, config, traffic, mesh, batch, used):
    """The compiler's own refusal is what bounds a train cell's batch."""
    import jax

    with pytest.raises(jax.errors.JaxRuntimeError,
                       match="RESOURCE_EXHAUSTED") as refusal:
        _train_step(config, traffic, mesh, topo.devices, batch=batch)
    assert used in str(refusal.value)


def test_internlm2_at_twice_the_batch_compiles_but_is_not_the_cell(topo):
    """8 x 4096 under fsdp=4 is accepted by the compiler, and its steps
    ran on the four chips; the cell keeps 4 x 4096 because the step's
    scratch then leaves the chip no room for the gradient check that
    ``correct`` needs (PERF.md sections 4 and 6)."""
    _train_step("internlm2-1.8b", "train-fsdp4", {"fsdp": 4}, topo.devices,
                batch=8)


# ----------------------------------------------------- the serve programs
def _engine_programs(cell_name, one_chip, max_slots=None):
    """Yield (label, thunk that compiles) for every program the engine of
    a serve cell warms: each prefill group x bucket, each decode bucket."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.serve import llm

    cell = _json("workloads", cell_name)
    config = _json("configs", cell["config"])
    engine = cell["engine"]
    slots = max_slots or engine["max_slots"]
    max_len, buckets = engine["max_len"], engine["prefill_buckets"]
    cfg = program.llama_config(config, max_seq_len=max_len)
    # The jitted programs close over the config only; a one-slot engine
    # with no weights hands them over without allocating anything.
    server = llm.LLMServer(
        model_preset=program.install_preset(config), max_slots=1,
        max_len=64, prefill_buckets=(64,), params={"x": jnp.zeros(1)},
        warmup=False)
    server.shutdown()
    params = _on(one_chip, jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, slots, max_len)))

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode_buckets, b = [], max(64, buckets[0])
    while b < max_len:
        decode_buckets.append(b)
        b *= 2
    decode_buckets.append(max_len)
    for s_active in decode_buckets:
        yield f"decode_k s_active={s_active}", (
            lambda s=s_active: server._decode_k.lower(
                params, cache, arr(jnp.int32, slots), arr(jnp.int32, slots),
                arr(jnp.int32, slots), arr(jnp.int32, slots),
                arr(jnp.bool_, slots), arr(jnp.bool_, slots),
                k=16, s_active=s).compile())
    for group in engine.get("prefill_groups", llm.PREFILL_GROUPS):
        yield f"prefill group={group} bucket={buckets[-1]}", (
            lambda g=group: server._prefill.lower(
                params, cache, arr(jnp.int32, g, buckets[-1]),
                arr(jnp.int32, g), arr(jnp.int32, g)).compile())


@pytest.mark.parametrize("cell", ["internlm2-1.8b.serve-batch-decode",
                                  "internlm2-1.8b.serve-chat-busy"])
def test_engine_programs_fit_one_chip(one_chip, cell):
    for label, compile_it in _engine_programs(cell, one_chip):
        compile_it()       # the compiler raises RESOURCE_EXHAUSTED if not


@pytest.mark.parametrize("cell,slots,refused,used", [
    # 128 x 512 compiles since the decode step updates the cache in place
    # (PR 24); the cell keeps 120 so that its ledger line is one series
    ("internlm2-1.8b.serve-batch-decode", 128, None, None),
    # 40 x 1,280 is the largest multiple of 8 that fits: at 48 the widest
    # prefill group's scratch beside weights and cache is over the chip
    ("internlm2-1.8b.serve-chat-busy", 48, "prefill group=32 bucket=1024",
     "16.64G of 15.75G"),
], ids=["serve-batch-decode-128", "serve-chat-busy-48"])
def test_eight_more_slots_compile_or_name_what_refuses(one_chip, cell, slots,
                                                       refused, used):
    """What bounds a serve cell's slot count, by the compiler's own
    message: every program the engine warms compiles at ``slots`` except
    ``refused`` (none: the cell is not at its bound, and says why)."""
    import jax

    assert slots == _json("workloads", cell)["engine"]["max_slots"] + 8
    for label, compile_it in _engine_programs(cell, one_chip,
                                              max_slots=slots):
        if label != refused:
            compile_it()
            continue
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="RESOURCE_EXHAUSTED") as refusal:
            compile_it()
        assert used in str(refusal.value)

"""The head's weight gradient in the two train cells' compiled steps, for a
v5e that is described, not attached (as ``tests/test_flash_stats_aot.py``
and ``benchmarks/tests/test_aot_real_widths.py`` compile).  Cell 2
(``internlm2-1.8b.train-fsdp4``, four chips): no weight-sized array is
summed whole -- every all-reduce of 2048 x 92544 elements or more sits in
one of XLA's ``%all-reduce-scatter`` fusions -- and the head's shard,
``f32[512,92544]``, reaches each chip by collective-permutes that are
started and awaited under ``head_loss``, before the layers' backward.
Cell 1 (one chip, no mesh): the parent's program, no collective in it.
Nothing runs, so nothing here is a speed.

Each step is compiled once, in a module-scoped fixture, and its text shared
by the cases; the topology is described inside a fixture and the compiles
run in the test's own process (the TPU library loads once, in the worker
that gets this file).
"""

import importlib
import math
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
MOSAIC = 'custom_call_target="tpu_custom_call"'
COLLECTIVES = r"\b(?:all-gather|all-reduce|reduce-scatter|all-to-all|" \
              r"collective-permute|collective-broadcast)(?:-start)?\("
HEAD = 2048 * 92544         # internlm2-1.8b's head, elements
SHARD = "f32[512,92544]"    # a chip's rows of its gradient, as summed


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


_COMPILED = {}      # (config, traffic) -> the step, for both files' fixtures


def _compiled(topo, config, traffic, mesh):
    """The cell's step compiled for the chip from a CPU backend: the flash
    kernels steered to Mosaic, the compile kept out of the persistent
    cache (which cannot read it back without a chip).  Compiled once a
    process: ``tests/test_layer_grad_aot.py`` reads the same steps."""
    import jax

    from benchmarks.tests.test_aot_real_widths import _train_step

    if (config, traffic) in _COMPILED:
        return _COMPILED[config, traffic]
    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(flash, "_use_interpret", lambda: False)
            step = _train_step(config, traffic, mesh, topo.devices)
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
    return _COMPILED.setdefault((config, traffic), step)


@pytest.fixture(scope="module")
def fsdp4(topo):
    return _compiled(topo, "internlm2-1.8b", "train-fsdp4", {"fsdp": 4})


@pytest.fixture(scope="module")
def one_chip(topo):
    return _compiled(topo, "smollm2-360m", "train-1chip", None)


def _instructions(hlo):
    """(name, result type text, opcode, whole line) of every instruction,
    inside fusions and out."""
    for line in hlo.splitlines():
        made = re.match(
            r"\s*(?:ROOT )?%([\w.-]+) = (\(.*?\)|\S+) ([a-z][a-z-]*)\(",
            line)
        if made:
            yield (*made.groups(), line)


def _elements(result):
    return max((math.prod(map(int, dims.split(","))) if dims else 1
                for dims in re.findall(r"\w+\[([\d,]*)\]", result)),
               default=0)


def _computations(hlo):
    """computation name -> its text."""
    return {m.group(1): m.group(0) for m in re.finditer(
        r"^(?:ENTRY )?%([\w.-]+) \(.*?^}", hlo, re.M | re.S)}


def test_no_weight_sized_array_is_summed_whole(fsdp4):
    """(a) Every all-reduce with a result of the head's size or more is
    the all-reduce of one of XLA's ``%all-reduce-scatter`` fusions, whose
    only user is the slice by ``partition-id`` beside it."""
    whole = []
    for name, text in _computations(fsdp4.as_text()).items():
        if name.startswith("all-reduce-scatter"):
            continue
        whole += [(name, n, result) for n, result, op, _ in
                  _instructions(text)
                  if op.startswith("all-reduce") and _elements(result) >= HEAD]
    assert not whole


def test_the_heads_shard_arrives_by_permutes_under_head_loss(fsdp4):
    """(b), (d) Three collective-permutes carry a chip's ``f32[512,92544]``
    rows of the other chips' partial products; they, the four matmuls
    that form the blocks and the sum that casts them are the head's
    backward's, by their ``op_name``."""
    entry = [i for i in _instructions(
        fsdp4.as_text().split("\nENTRY ", 1)[1])]
    starts = [line for _, result, op, line in entry
              if op == "collective-permute-start" and SHARD in result]
    dones = [line for _, result, op, line in entry
             if op == "collective-permute-done" and result.startswith(SHARD)]
    assert len(starts) == len(dones) == 3
    blocks = [line for _, result, op, line in entry
              if op == "fusion" and result.startswith(SHARD)
              and "/shard_map/dot_general" in line]
    assert len(blocks) == 4
    summed = [line for _, result, op, line in entry
              if result.startswith("bf16[512,92544]") and "/shard_map/" in line]
    assert len(summed) == 1
    for line in starts + dones + blocks + summed:
        assert 'op_name="jit(step)/transpose(jvp(head_loss))/shard_map/' \
            in line, line[:200]


def test_the_exchange_is_over_before_the_layers_backward(fsdp4):
    """The permutes are started behind the blocks they carry and awaited
    before the backward's layer loop, with this chip's own block and the
    input's gradient formed in between: nothing of them rides beside the
    loop's own exchanges (the layers' permutes and the q, k and v
    projections' reduce-scatters, ``tests/test_layer_grad_aot.py``) or the
    embedding's reduce-scatter."""
    entry = list(_instructions(fsdp4.as_text().split("\nENTRY ", 1)[1]))
    at = {}
    for i, (name, result, op, line) in enumerate(entry):
        if op == "while" and "transpose(jvp(layer_scan))" in line:
            at["loop"] = i
        elif SHARD in result and op.startswith("collective-permute-"):
            at.setdefault(op, []).append(i)
        elif op == "fusion" and "transpose(jvp(head_loss))/dot_general" in line:
            at["dx"] = i
        elif (op == "fusion" and result.startswith(SHARD)
              and "/shard_map/dot_general" in line):
            at.setdefault("block", []).append(i)
    first_start = min(at["collective-permute-start"])
    first_done = min(at["collective-permute-done"])
    assert max(at["collective-permute-done"]) < at["loop"]
    assert all(block < first_start                         # the sent blocks
               for block in sorted(at["block"])[:3])
    assert first_start < max(at["block"]) < first_done     # this chip's own
    assert first_start < at["dx"] < first_done


def test_the_step_is_otherwise_the_cells(fsdp4):
    """(c) As ``test_internlm2_step_fits_four_chips`` holds them: three
    Mosaic calls, the parameter gathers, a quarter of the state a chip."""
    hlo = fsdp4.as_text()
    assert hlo.count(MOSAIC) == 3
    assert len(re.findall(r"\ball-gather(?:-start)?\(", hlo)) >= 24
    assert not re.findall(r"\breduce-scatter(?:-start)?\(", hlo)
    per_chip = fsdp4.memory_analysis().argument_size_in_bytes
    assert 5.6e9 < per_chip < 5.8e9


def test_one_chip_compiles_the_plain_head(one_chip):
    """Cell 1 has no mesh: no collective of any kind, no ``shard_map`` in
    an ``op_name``, three Mosaic calls."""
    hlo = one_chip.as_text()
    assert not re.findall(COLLECTIVES, hlo)
    assert "shard_map" not in hlo
    assert hlo.count(MOSAIC) == 3

"""The flash kernels' softmax statistics at the train cells' real shapes,
compiled for a v5e that is described, not attached (as
``benchmarks/tests/test_aot_real_widths.py`` compiles): ``lse`` and
``delta`` reach the three Mosaic calls lane-dense, ``(B, H, 1, S)``
float32, and nothing of ``(B, H, S, 1)`` — which the tiled layout pads
128-fold — is made anywhere in the program; and the backward does its own
GQA: dq and dk/dv take K and V per KV head and give dq per q head, dk and
dv per KV head, in the model's type, so no float32 gradient and no K or V
expanded to the q heads crosses HBM.  Nothing runs, so nothing here is a
speed.  And the rows' lengths are the serving forward's alone: training's
three calls take the operands they took (no ``s32`` among them), the
prefill call at cell 8's and cell 7's shapes takes one ``s32[B]`` more
and returns what it returned.

The topology is described inside a fixture and the compiles run in the
test's own process: the TPU library loads once, in the worker that gets
this file.
"""

import importlib
import math
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")
MOSAIC = 'custom_call_target="tpu_custom_call"'


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


def _mosaic_calls(hlo, kernels=r"flash_attention_\w+?"):
    """kernel name -> (result types, operand types) of its custom call."""
    calls = {}
    for line in hlo.splitlines():
        if MOSAIC not in line:
            continue
        name = re.match(rf"\s*(?:ROOT )?%({kernels})[.\d]* = ",
                        line).group(1)
        result = line.split(" custom-call(")[0]
        operands = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                             line).group(1)
        calls[name] = tuple(re.findall(r"\w+\[[\d,]*\]", part)
                            for part in (result.split(" = ")[1], operands))
    return calls


def _column_values(hlo, seq):
    """Every float32 value of the program whose last two dimensions are
    ``(seq, 1)``: a row's statistic as a column."""
    return set(re.findall(rf"f32\[(?:\d+,)*{seq},1\]", hlo))


def _held_to_the_lane_dense_layout(hlo, batch, seq, heads):
    stats = f"f32[{batch},{heads},1,{seq}]"
    calls = _mosaic_calls(hlo)
    assert set(calls) == {"flash_attention_fwd", "flash_attention_dq",
                          "flash_attention_dkdv"}
    assert calls["flash_attention_fwd"][0][1:] == [stats]        # o, lse
    for kernel in ("flash_attention_dq", "flash_attention_dkdv"):
        assert calls[kernel][1][4:] == [stats, stats]            # lse, delta
    assert not _column_values(hlo, seq)


def _held_to_the_kernels_own_gqa(hlo, batch, seq, heads, kv_heads, head_dim):
    """The three Mosaic calls' types: K and V reach every one of them per
    KV head, and the gradients leave dq and dk/dv in bf16 at their
    consumer's granularity."""
    per_q = f"bf16[{batch},{heads},{seq},{head_dim}]"
    per_kv = f"bf16[{batch},{kv_heads},{seq},{head_dim}]"
    calls = _mosaic_calls(hlo)
    assert calls["flash_attention_dq"][0] == [per_q]
    assert calls["flash_attention_dkdv"][0] == [per_kv, per_kv]
    for results, operands in calls.values():
        assert operands[:3] == [per_q, per_kv, per_kv]           # q, k, v
        # nor a row's length: training's rows are full
        assert not [o for o in operands if o.startswith("s32")]


def _entry_values(hlo):
    """name -> (result type text, operand names, is a Mosaic call) of
    every instruction of the ENTRY computation: the values that exist in
    HBM, not those inside a fusion."""
    entry = hlo[hlo.index("\nENTRY "):]
    values = {}
    for line in entry.splitlines()[1:]:
        made = re.match(r"\s*(?:ROOT )?%([\w.-]+) = (.*?) [\w-]+\((.*)", line)
        if made:
            name, result, rest = made.groups()
            values[name] = (result, re.findall(r"%([\w.-]+)", rest),
                            MOSAIC in line)
    return values


def _elements(result):
    return [math.prod(map(int, dims.split(","))) if dims else 1
            for dims in re.findall(r"\w+\[([\d,]*)\]", result)]


def _nothing_per_q_head_but_what_the_kernels_want(hlo, batch, seq, heads,
                                                  head_dim):
    """Of the values in HBM none is ``f32[B, Hq, S, D]`` (a gradient
    written to be summed or rounded by XLA) and none of that many elements
    is made from K or V outside a kernel (an expansion to the q heads,
    under whatever shape)."""
    values = _entry_values(hlo)
    assert f"f32[{batch},{heads},{seq},{head_dim}]" not in " ".join(
        result for result, _, _ in values.values())
    from_kv = set()
    for name, (result, operands, mosaic) in values.items():   # in order
        if name in ("k.1", "v.1") or (
                not mosaic and from_kv.intersection(operands)):
            from_kv.add(name)
            assert batch * heads * seq * head_dim not in _elements(result), \
                (name, result)
    assert {"k.1", "v.1"} < from_kv


@pytest.mark.parametrize("batch,seq,heads,kv_heads,head_dim", [
    (8, 2048, 15, 5, 64),     # smollm2-360m.train-1chip
    (1, 4096, 16, 8, 128),    # internlm2-1.8b.train-fsdp4, one chip's share
])
def test_the_gradient_holds_no_statistics_column(
        topo, compiled_for_the_chip, batch, seq, heads, kv_heads, head_dim):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True).astype(
            jnp.float32).sum()

    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((batch, seq, heads, head_dim), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((batch, seq, kv_heads, head_dim),
                              jnp.bfloat16, sharding=one_chip)
    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    _held_to_the_lane_dense_layout(hlo, batch, seq, heads)
    _held_to_the_kernels_own_gqa(hlo, batch, seq, heads, kv_heads, head_dim)
    _nothing_per_q_head_but_what_the_kernels_want(hlo, batch, seq, heads,
                                                  head_dim)


def test_the_smollm2_step_saves_the_kernels_own_operand(
        topo, compiled_for_the_chip):
    """Cell 1's whole step under ``remat_policy="attn"``: the ``lse`` the
    policy saves, stacked over the 32 layers, IS the operand of dq and
    dk/dv (no pack before the save, no unpack after it), and the step
    makes no statistics column either."""
    from benchmarks.tests.test_aot_real_widths import _train_step

    hlo = _train_step("smollm2-360m", "train-1chip", None,
                      topo.devices).as_text()
    _held_to_the_lane_dense_layout(hlo, 8, 2048, 15)
    _held_to_the_kernels_own_gqa(hlo, 8, 2048, 15, 5, 64)
    assert "f32[32,8,15,1,2048]" in hlo          # the saved residual
    assert not re.search(r"f32\[32,8,15,\d+,128\]", hlo)   # nor a packed one


@pytest.mark.parametrize("heads,kv_heads,d,dv,options,result", [
    # deepseek-v2.serve-long-prompt: a group of the expanded latent heads
    (32, 32, 192, 128, dict(lse=False), ["bf16[1,32,12288,128]"]),
    # smallthinker-21b-a3b.serve-long-prompt: a window layer, lse written
    (28, 4, 128, 128, dict(window=4096),
     ["bf16[1,28,12288,128]", "f32[1,28,1,12288]"]),
])
def test_the_prefill_call_takes_the_lengths_and_returns_what_it_returned(
        topo, compiled_for_the_chip, heads, kv_heads, d, dv, options, result):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.flash_attention import flash_prefill_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    shapes = [jax.ShapeDtypeStruct((1, 12288, h, w), jnp.bfloat16,
                                   sharding=one_chip)
              for h, w in ((heads, d), (kv_heads, d), (kv_heads, dv))]
    lengths = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    qkv = [f"bf16[1,{h},12288,{w}]"
           for h, w in ((heads, d), (kv_heads, d), (kv_heads, dv))]

    def call(q, k, v, lengths=None):
        return flash_prefill_attention(q, k, v, scale=d ** -0.5,
                                       lengths=lengths, **options)

    untold = _mosaic_calls(jax.jit(call).lower(*shapes).compile().as_text(),
                           "flash_prefill_attention")
    told = _mosaic_calls(
        jax.jit(call).lower(*shapes, lengths).compile().as_text(),
        "flash_prefill_attention")
    assert untold == {"flash_prefill_attention": (result, qkv)}
    assert told == {"flash_prefill_attention": (result, ["s32[1]"] + qkv)}


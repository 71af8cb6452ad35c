"""The flash kernels' softmax statistics at the train cells' real shapes,
compiled for a v5e that is described, not attached (as
``benchmarks/tests/test_aot_real_widths.py`` compiles): ``lse`` and
``delta`` reach the three Mosaic calls lane-dense, ``(B, H, 1, S)``
float32, and nothing of ``(B, H, S, 1)`` — which the tiled layout pads
128-fold — is made anywhere in the program.  Nothing runs, so nothing here
is a speed.

The topology is described inside a fixture and the compiles run in the
test's own process: the TPU library loads once, in the worker that gets
this file.
"""

import importlib
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


def _mosaic_calls(hlo):
    """kernel name -> (result types, operand types) of its custom call."""
    calls = {}
    for line in hlo.splitlines():
        if MOSAIC not in line:
            continue
        name = re.match(r"\s*(?:ROOT )?%(flash_attention_\w+?)[.\d]* = ",
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
    assert "f32[32,8,15,1,2048]" in hlo          # the saved residual
    assert not re.search(r"f32\[32,8,15,\d+,128\]", hlo)   # nor a packed one

"""Cell 11's Mamba-1 mixer at the REAL widths (``phi-4-mini-flash-reasoning``:
5,120 channels of 16 state dimensions, a row of 4,096 / 8,192 / 12,288
positions) for a v5e that is described, not attached: ``mamba1.prefill``
runs the recurrence as the Mosaic kernel ``mamba1_scan`` under scope
``mamba1_scan`` (what ``sambay_ssm_scan_time_share`` sums) with no loop left
in the program, and the toy presets' 128 channels keep XLA's loop.  The whole
prefill and decode programs of the cell are compiled by
``benchmarks/tests/test_phi4flash_cell.py``.  Nothing runs, so nothing here
is a speed.
"""

import os

import pytest

from benchmarks.lib import program
# ``topo`` is described inside that file's fixture (never at import);
# ``compiled_kernels`` keeps these compiles out of the persistent cache.
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    MOSAIC, _json, _on, compiled_kernels, kernels_by_name_and_scope,
    one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "phi-4-mini-flash-reasoning"
CELL = "phi-4-mini-flash-reasoning.serve-long-prompt"


def _mixer(one_chip, cfg, bucket, rows=1):
    """One Mamba-1 layer's ``prefill`` over ``rows`` rows of ``bucket``
    positions, compiled for the described chip."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, mamba1

    params = jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0))
    layer = _on(one_chip, {
        k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
        for k, v in params["layers"].items() if k.startswith("ssm_")})
    h = jax.ShapeDtypeStruct((rows, bucket, cfg.hidden_size), jnp.float32,
                             sharding=one_chip)
    lengths = jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=one_chip)
    return jax.jit(lambda h, layer, lengths: mamba1.prefill(
        h, layer, cfg, lengths)).lower(h, layer, lengths).compile()


@pytest.mark.parametrize("bucket", _json("workloads", CELL)["engine"][
    "prefill_buckets"])
def test_a_layer_of_the_cell_runs_the_scan_kernel(one_chip, bucket):
    from ray_tpu.ops import mamba1_scan

    cfg = program.llama_config(_json("configs", CONFIG), max_seq_len=16384)
    assert (cfg.ssm_inner, cfg.ssm_state) == (5120, 16)
    assert mamba1_scan.engages(cfg.ssm_inner, 1)
    assert mamba1_scan.padded_len(bucket) == bucket
    compiled = _mixer(one_chip, cfg, bucket)
    text = compiled.as_text()
    assert kernels_by_name_and_scope(text) == {
        ("mamba1_scan", "mamba1_scan"): 1}
    assert " while(" not in text
    # u, dt and y in float32 and the projections' results beside them: no
    # (P, N, Di) tensor (4 GB at 12,288 positions)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 << 30


def test_a_group_of_several_rows_compiles_to_the_loop(one_chip):
    """A prefill program of 4 rows x 512 or 4 x 1,024 positions with the
    call in it did not return on the chip, whatever the call's body
    (PERF.md section 6 (g), PR 62): a group keeps the program it had."""
    cfg = program.llama_config(_json("configs", CONFIG), max_seq_len=16384)
    text = _mixer(one_chip, cfg, 512, 4).as_text()
    assert MOSAIC not in text and " while(" in text


@pytest.mark.parametrize("rows,bucket", [(1, 512), (1, 1024), (1, 12288)])
def test_the_call_keeps_to_the_default_scoped_vmem(one_chip, rows, bucket):
    """The single rows an engine may warm beside the cell's
    (``sambay_check.py``'s 512 and 1,024): compiled with ``vmem_limit_bytes``
    = 32 MiB the call had a scoped window of its own (PERF.md section 6 (g),
    PR 62); its tiles are 9.7 MB, inside the window every fusion has."""
    import json
    import re

    cfg = program.llama_config(_json("configs", CONFIG), max_seq_len=16384)
    text = _mixer(one_chip, cfg, bucket, rows).as_text()
    calls = [line for line in text.splitlines()
             if "mamba1_scan" in line and " custom-call(" in line]
    assert len(calls) == rows
    for call in calls:
        config = json.loads(re.search(r"backend_config=(\{.*\})\s*$",
                                      call.strip()).group(1))
        assert not config.get("scoped_memory_configs")
        for used in config.get("used_scoped_memory_configs", []):
            assert int(used["offset"]) == 0 and int(used["size"]) < 16 << 20
        assert f"f32[1,{bucket},5120]" in call.split(" custom-call(")[0]


def test_channels_that_are_no_whole_block_compile_to_the_loop(one_chip):
    from ray_tpu.models.llama import LlamaConfig

    cfg = LlamaConfig.debug(
        n_layers=2, layer_types=("mamba1", "attention"), hidden_size=64,
        ssm_inner=128, ssm_state=16, ssm_dt_rank=4, ssm_conv=4, ssm_chunk=4,
        rope=False)
    text = _mixer(one_chip, cfg.parts()[0][0], 64).as_text()
    assert MOSAIC not in text and " while(" in text

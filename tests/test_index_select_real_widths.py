"""Cell 10's selection of keys at the REAL widths (``keye-vl-2.0-30b-a3b``:
16 index heads of 64, ``topk`` 2,048, a row of 4,096 / 8,192 / 12,288
positions) for a v5e that is described, not attached:
``indexer.prefill_keep`` is the Mosaic kernel ``index_select_prefill`` under
scope ``index_select`` (what ``dsa_prefill_selection_time_share`` sums),
with no loop and no ``(512, P)`` float32 tile left in the program, inside
the VMEM its call asks for; a group of several rows keeps XLA's form.  The
whole prefill and decode programs of the cell are compiled by
``benchmarks/tests/test_keye_cell.py``.  Nothing runs, so nothing here is a
speed.
"""

import os

import pytest

from benchmarks.lib import program
# ``topo`` is described inside that file's fixture (never at import);
# ``compiled_kernels`` keeps these compiles out of the persistent cache.
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    MOSAIC, _json, compiled_kernels, kernels_by_name_and_scope, one_chip,
    topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "keye-vl-2.0-30b-a3b"
CELL = "keye-vl-2.0-30b-a3b.serve-long-prompt"


def _selection(one_chip, cfg, bucket, rows=1):
    """``indexer.prefill_keep`` over ``rows`` rows of ``bucket`` positions
    at ``cfg``'s index widths, compiled for the described chip."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import indexer

    def arr(dtype, *dims):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return jax.jit(lambda qi, ki_t, w, lengths: indexer.prefill_keep(
        qi, ki_t, w, cfg.index_topk, lengths)).lower(
        arr(cfg.dtype, rows, bucket, cfg.index_heads, cfg.index_head_dim),
        arr(cfg.dtype, rows, cfg.index_head_dim, bucket),
        arr(jnp.float32, rows, bucket, cfg.index_heads),
        arr(jnp.int32, rows)).compile()


@pytest.fixture(scope="module")
def cfg():
    cfg = program.llama_config(_json("configs", CONFIG), max_seq_len=16384)
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) \
        == (16, 64, 2048)
    return cfg


@pytest.mark.parametrize("bucket", _json("workloads", CELL)["engine"][
    "prefill_buckets"])
def test_a_row_of_the_cell_selects_through_the_kernel(one_chip, cfg, bucket):
    from ray_tpu.models import indexer
    from ray_tpu.ops import index_select

    assert index_select.engages(1, bucket, cfg.index_topk, cfg.index_heads,
                                cfg.index_head_dim, indexer.QUERY_TILE)
    compiled = _selection(one_chip, cfg, bucket)
    text = compiled.as_text()
    assert kernels_by_name_and_scope(text) == {
        ("index_select_prefill", "index_select"): 1}
    assert " while(" not in text and f"f32[1,512,{bucket}]" not in text
    # the operands and the mask alone: nothing of a tile's scores in HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


def test_a_group_of_several_rows_keeps_xlas_form(one_chip, cfg):
    """A program of several rows with a Mosaic call in it did not return on
    the chip (PERF.md section 6 (g), PR 62): a group keeps the program it
    had, the tiles a ``lax.map`` of ``scores`` + ``topk_keep``."""
    text = _selection(one_chip, cfg, 4096, rows=4).as_text()
    assert MOSAIC not in text and " while(" in text

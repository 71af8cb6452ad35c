"""Cell 12's programs at the REAL widths (``solar-open2-250b``: 64 KDA heads
of 128, chunks of 64, 64 slots x 16,384) for a v5e that is described, not
attached, as ``benchmarks/tests/test_solar_open2_cell.py`` compiles them:
the 12,288 prefill runs the chunked delta rule as the Mosaic kernel
``kda_chunk`` under scope ``kda_chunk`` (what
``kda_prefill_chunk_time_share`` sums), with no library triangular solve
left in it, inside the scratch the cell's own test allows; the decode
program, which the kernel does not touch, lowers to the text it lowered
to before.  Nothing runs, so nothing here is a speed.
"""

import hashlib
import os
import re

import pytest

from benchmarks.lib import program
# ``topo`` is described inside that file's fixture (never at import);
# ``compiled_kernels`` keeps these compiles out of the persistent cache.
from benchmarks.tests.test_aot_real_widths import (  # noqa: F401
    _json, _on, compiled_kernels, kernels_by_name_and_scope, one_chip, topo)

os.environ.setdefault("TPU_LOG_DIR", "disabled")
CONFIG = "solar-open2-250b"
CELL = "solar-open2-250b.serve-long-prompt"
# sha256 (first 16 hex digits) of the decode program's lowered text at the
# commit before the kernel (1de62b7), without the Mosaic kernels'
# serialized bodies: those carry the checkout's own file paths, and the
# two files they are built from (``ops/decode_attention.py``,
# ``ops/kda_state_update.py``) are that commit's -- and since PR 63 with the
# expert layer's one un-sort and sum, which a share takes too: a token's k-th
# result is gathered straight into the float32 sum (``moe._sorted_ffn``; at
# c19b209, before it, the text was that commit's still, ced9784b7a45767a)
DECODE_BEFORE = "4e700bf7e6e82721"
MOSAIC_BODY = re.compile(r'\\22body\\22: \\22[^\\]*\\22')


@pytest.fixture(scope="module")
def engine(one_chip):
    """(cfg, params, cache, arr) of the cell's engine, as shapes on the
    described chip."""
    import jax

    from ray_tpu.models import llama, llama_serve

    shape = _json("workloads", CELL)["engine"]
    cfg = program.llama_config(_json("configs", CONFIG),
                               max_seq_len=shape["max_len"])
    params = _on(one_chip, jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: llama_serve.init_cache(cfg, shape["max_slots"],
                                       shape["max_len"])))

    def arr(dtype, *dims):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return cfg, shape, params, cache, arr


def test_the_widest_prefill_runs_the_chunk_kernel(engine):
    import jax.numpy as jnp

    from ray_tpu.models import llama_serve
    from ray_tpu.ops import kda_chunk

    cfg, shape, params, cache, arr = engine
    assert kda_chunk.engages(cfg.kda_head_dim, cfg.kda_chunk)
    bucket = max(shape["prefill_buckets"])
    assert bucket == 12288
    prefill = llama_serve.build_prefill(cfg).lower(
        params, cache, arr(jnp.int32, 1, bucket), arr(jnp.int32, 1),
        arr(jnp.int32, 1)).compile()
    text = prefill.as_text()
    kernels = kernels_by_name_and_scope(text)
    assert kernels["kda_chunk", "kda_chunk"] >= 1
    assert kernels["flash_prefill_attention", "flash_attention.fwd"] >= 1
    # the library triangular solve of XLA's form, by its result's shape
    assert "f32[16,1,64,1,64,64]" not in text
    assert prefill.memory_analysis().temp_size_in_bytes < 4 << 30


def test_the_decode_program_is_the_one_before(engine):
    import jax.numpy as jnp

    from ray_tpu.models import llama_serve

    cfg, shape, params, cache, arr = engine
    slots = shape["max_slots"]
    text = llama_serve.build_decode_k(cfg).lower(
        params, cache, arr(jnp.int32, slots), arr(jnp.int32, slots),
        arr(jnp.int32, slots), arr(jnp.int32, slots), arr(jnp.bool_, slots),
        arr(jnp.bool_, slots), k=16, s_active=shape["max_len"]).as_text()
    assert "kda_state_update" in text and "kda_chunk" not in text
    text, bodies = MOSAIC_BODY.subn("body", text)
    assert bodies >= 4      # the decode attention and three state updates
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == DECODE_BEFORE

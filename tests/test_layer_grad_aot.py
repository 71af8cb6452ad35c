"""The layers' weight gradients in the two train cells' compiled steps, for
a v5e that is described, not attached (beside ``tests/test_head_grad_aot.py``,
whose fixtures and helpers these cases share).  Cell 2
(``internlm2-1.8b.train-fsdp4``, four chips): inside the backward loop's
body three of XLA's ``%all-reduce-scatter`` fusions are left where the
parent had seven -- the
q, k and v projections', which ``llama._qkv_rope`` leaves to XLA -- and the
gradients of ``wo``, ``w_gate``, ``w_up`` and ``w_down`` reach each chip by
collective-permutes of a quarter of the partial product, started and
awaited inside that body under the scope of the matmul they belong to.
Cell 1 (one chip, no mesh) holds no collective; and with no mesh a serve
program lowers to the text that plain ``matmul`` gives.  Nothing runs, so
nothing here is a speed.

The topology is described inside that file's fixture and each step compiled
once, in the test's own process: its ``_compiled`` keeps what it compiled,
so a worker that runs both files compiles a cell's step for both.
"""

import re

import pytest

from test_head_grad_aot import (COLLECTIVES, MOSAIC,  # noqa: F401
                                _computations, _instructions, fsdp4,
                                one_chip, topo)
from tools.collectives_alone import FUSED_REDUCE_SCATTER

# A chip's part of each exchanged gradient as summed, float32, and the
# scope its permutes and blocks are traced under: w_gate / w_up rows,
# w_down and wo columns.
EXCHANGED = {"f32[512,8192]": ("ffn", 2), "f32[8192,512]": ("ffn", 1),
             "f32[2048,512]": ("attn_out", 1)}
LEFT_TO_XLA = 3             # wq, wk, wv


@pytest.fixture(scope="module")
def backward_body(fsdp4):
    """The instructions of the backward layer loop's body, in the order
    the compiler scheduled them."""
    hlo = fsdp4.as_text()
    loop = next(line for _, _, op, line in _instructions(
        hlo.split("\nENTRY ", 1)[1])
        if op == "while" and "transpose(jvp(layer_scan))" in line)
    body = re.search(r"\bbody=%([\w.-]+)", loop).group(1)
    return list(_instructions(_computations(hlo)[body]))


def test_the_loop_keeps_three_fused_reduce_scatters(fsdp4, backward_body):
    """Seven in the parent, a layer's q, k and v projections' now; the
    step's only other one is the embedding table's, outside the loop."""
    fused = [line for _, _, _, line in backward_body
             if FUSED_REDUCE_SCATTER in line]
    assert len(fused) == LEFT_TO_XLA
    assert all("/qkv_proj/" in line for line in fused)
    assert fsdp4.as_text().count(FUSED_REDUCE_SCATTER) == LEFT_TO_XLA + 1


@pytest.mark.parametrize("shard", list(EXCHANGED))
def test_a_layers_shards_arrive_by_permutes_inside_the_loop(backward_body,
                                                            shard):
    """Three collective-permutes a weight carry a chip's part of the other
    chips' float32 partial products; each is started and awaited inside
    the body, behind the three blocks that are sent and with matmuls
    scheduled beneath the flight, all under the matmul's own scope."""
    scope, weights = EXCHANGED[shard]
    at = {}
    for i, (_, result, op, line) in enumerate(backward_body):
        if shard not in result:
            continue
        if op.startswith("collective-permute-"):
            at.setdefault(op, []).append(i)
            assert f"/checkpoint/{scope}/shard_map/ppermute" in line
        elif op == "fusion" and "/shard_map/dot_general" in line:
            at.setdefault("block", []).append(i)
            assert f"/checkpoint/{scope}/shard_map/" in line
    assert len(at["collective-permute-start"]) == 3 * weights
    assert len(at["collective-permute-done"]) == 3 * weights
    assert len(at["block"]) == 4 * weights
    first_start = min(at["collective-permute-start"])
    last_done = max(at["collective-permute-done"])
    assert sorted(at["block"])[2] < first_start          # the sent blocks
    assert any(op == "fusion" and "/dot_general" in line  # matmuls beneath
               for _, _, op, line in backward_body[first_start:last_done])


def test_the_step_holds_no_other_weight_sized_permute(fsdp4):
    """Outside the loop's body the only float32 permutes are the head's
    three (``tests/test_head_grad_aot.py``)."""
    entry = list(_instructions(fsdp4.as_text().split("\nENTRY ", 1)[1]))
    starts = [result for _, result, op, _ in entry
              if op == "collective-permute-start" and "f32[" in result]
    assert len(starts) == 3 and all("f32[512,92544]" in r for r in starts)


def test_one_chip_compiles_no_exchange(one_chip):
    """Cell 1 has no mesh: ``scattered_grad_matmul`` is ``matmul``."""
    hlo = one_chip.as_text()
    assert not re.findall(COLLECTIVES, hlo)
    assert "shard_map" not in hlo and FUSED_REDUCE_SCATTER not in hlo
    assert hlo.count(MOSAIC) == 3


@pytest.mark.parametrize("program", ["decode_k", "prefill"])
def test_a_serve_program_lowers_to_the_plain_matmuls_text(program,
                                                          monkeypatch):
    """``internlm2-1.8b``'s serve programs (no mesh, forward only): the
    text lowered through ``scattered_grad_matmul`` is the text lowered
    with ``matmul`` in its place, which is what the parent's sites call."""
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import program as bench_program
    from benchmarks.tests.test_aot_real_widths import _json
    from ray_tpu.models import llama, llama_serve

    cfg = bench_program.llama_config(_json("configs", "internlm2-1.8b"),
                                     max_seq_len=512)
    slots = 8
    params = jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0))
    cache = jax.eval_shape(lambda: llama.init_kv_cache(cfg, slots, 512))

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    def lowered():
        if program == "prefill":
            return llama_serve.build_prefill(cfg).lower(
                params, cache, arr(jnp.int32, 4, 256), arr(jnp.int32, 4),
                arr(jnp.int32, 4)).as_text()
        return llama_serve.build_decode_k(cfg).lower(
            params, cache, *[arr(jnp.int32, slots)] * 4,
            arr(jnp.bool_, slots), arr(jnp.bool_, slots),
            k=4, s_active=512).as_text()

    ours = lowered()
    monkeypatch.setattr(llama, "scattered_grad_matmul",
                        lambda x, w, w_axes: llama.matmul(x, w))
    assert ours == lowered()
    assert "custom_vjp" not in ours and "shard_map" not in ours

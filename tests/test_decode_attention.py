"""``ops/decode_attention.py`` interpreted on the CPU against
``llama._cache_attend`` over the layer's attended prefix: the same keys,
the same precisions, another order of summation."""

import importlib

import numpy as np
import pytest

# name: L, B, S, Hq, Hkv, D, layer, s_active
_SHAPES = {
    "gqa_16_8_x128": (2, 8, 512, 16, 8, 128, 0, 512),
    "mha_16_16_x128": (2, 8, 256, 16, 16, 128, 0, 256),
    "toy_hkv2_d16": (2, 8, 128, 4, 2, 16, 0, 128),
    "layer_2_of_a_stack": (3, 8, 128, 4, 2, 16, 2, 128),
    "bucket_shorter_than_cache": (2, 8, 512, 16, 8, 128, 1, 256),
    "block_does_not_divide_cache": (1, 8, 200, 16, 8, 128, 0, 200),
    # the benchmark's geometries the cases above lack, handed over AS rows
    # (``_AS_ROWS``): cell 11's paired rows with a cache length its block
    # does not divide, cell 7's, cell 10's with a learned selection
    # (``_KEPT``), cell 11's ring with rows longer than the ring; and ten
    # rows a position under a selection, which no cell runs yet
    "paired_rows_40_10_x128": (2, 8, 200, 40, 10, 128, 1, 200),
    "rows_28_4_x128": (1, 8, 512, 28, 4, 128, 0, 512),
    "rows_32_4_x128_with_keep": (2, 8, 512, 32, 4, 128, 1, 384),
    "ring_512_of_longer_rows": (2, 8, 512, 40, 10, 128, 1, 16384),
    "paired_rows_with_keep": (1, 8, 200, 40, 10, 128, 0, 160),
    # heads of 64 kept two a 128-lane row (``_HEAD_64``: cells 9's and 6's
    # GQA 32/8, one row a position, MHA; the pool handed over as
    # ``init_cache`` stores it, the queries widened by ``_attend_rows``)
    "head_64_gqa_32_8": (2, 8, 512, 32, 8, 64, 1, 512),
    "head_64_gqa_8_2": (1, 8, 2048, 8, 2, 64, 0, 2048),
    "head_64_mha_4_4": (2, 8, 1024, 4, 4, 64, 1, 768),
    "head_64_gqa_32_8_with_keep": (1, 8, 512, 32, 8, 64, 0, 384),
}
_AS_ROWS = {"paired_rows_40_10_x128", "rows_28_4_x128",
            "rows_32_4_x128_with_keep", "ring_512_of_longer_rows",
            "paired_rows_with_keep"}
_HEAD_64 = {name for name in _SHAPES if name.startswith("head_64")}
_KEPT = {"rows_32_4_x128_with_keep", "paired_rows_with_keep",
         "head_64_gqa_32_8_with_keep"}
_TOL = 2e-2       # bf16: eight bits of mantissa on values of order one


def _lengths(bk, s_active):
    """0, 1, around a block's edge, the bucket's last position, past it."""
    return np.asarray([0, 1, bk - 1, bk, bk + 1, s_active - 1,
                       s_active, s_active + 7], np.int32)


def _kept(name, B, s_active):
    """A learned selection for the shapes that take one: four keys in ten,
    a row's first among them, none of a row's first block but one."""
    if name not in _KEPT:
        return None
    keep = np.random.default_rng(len(name)).random((B, s_active)) < 0.4
    keep[3, :min(300, s_active - 8)] = False
    keep[:, 0] = True
    return keep


def _inputs(shape, seed=0):
    import jax
    import jax.numpy as jnp

    L, B, S, hq, hkv, d, _layer, _s_active = shape
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(kq, (B, hq, d), jnp.bfloat16),
            jax.random.normal(kk, (L, B, S, hkv, d), jnp.bfloat16),
            jax.random.normal(kv, (L, B, S, hkv, d), jnp.bfloat16))


def _reference(q, ck, cv, layer, lens, active, s_active, keep=None):
    import jax.numpy as jnp

    from ray_tpu.models import llama

    s_active = min(s_active, ck.shape[2])
    selection = () if keep is None else (
        jnp.broadcast_to(jnp.arange(s_active), keep.shape),
        jnp.asarray(keep))
    out = llama._cache_attend(
        q[:, None], ck[layer, :, :s_active], cv[layer, :, :s_active],
        lens[:, None], q.shape[-1] ** -0.5, *selection)[:, 0]
    return jnp.where(active[:, None, None], out, 0)


@pytest.mark.parametrize("rows", ["edge_lengths", "some_inactive",
                                  "nan_past_the_length"])
@pytest.mark.parametrize("name", list(_SHAPES))
def test_kernel_agrees_with_cache_attend(name, rows):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama_serve
    from ray_tpu.ops import decode_attention as da

    shape = _SHAPES[name]
    L, B, S, hq, hkv, d, layer, s_active = shape
    q, ck, cv = _inputs(shape, seed=len(name))
    # rows a position and their width, as the pool is handed over
    rows, width = (hkv // 2, 2 * d) if name in _HEAD_64 else (hkv, d)
    bk = da.block_k(S, rows, width, ck.dtype.itemsize)
    lens = jnp.asarray(_lengths(min(bk, s_active - 2), s_active))
    active = jnp.asarray(
        [True, False, True, True, False, True, True, False]
        if rows == "some_inactive" else [True] * B)
    keep = _kept(name, B, s_active)
    want = _reference(q, ck, cv, layer, lens, active, s_active, keep)
    if rows == "nan_past_the_length":
        # What lies past a row's last key must be masked by selection:
        # a probability of zero times NaN is NaN.
        past = jnp.arange(S)[None, :] > lens[:, None]
        ck, cv = (jnp.where(past[None, :, :, None, None], jnp.nan, c)
                  for c in (ck, cv))
    as_rows = {}
    if name in _AS_ROWS | _HEAD_64:
        ck, cv = (c.reshape(L, B, S * rows, width) for c in (ck, cv))
        as_rows = dict(hkv=rows)
    got = jax.jit(llama_serve._attend_rows if name in _HEAD_64
                  else da.decode_attention,
                  static_argnames=("s_active", "scale", "hkv"))(
        q, ck, cv, jnp.int32(layer), lens, active, s_active=s_active,
        scale=d ** -0.5, keep=None if keep is None else jnp.asarray(keep),
        **as_rows)
    assert got.shape == want.shape and got.dtype == cv.dtype
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=_TOL, rtol=_TOL)
    assert (got[~np.asarray(active)] == 0).all()


def test_nothing_active_gives_zeros_and_reads_nothing():
    import jax.numpy as jnp

    from ray_tpu.ops import decode_attention as da

    shape = _SHAPES["toy_hkv2_d16"]
    q, ck, cv = _inputs(shape)
    nan = jnp.full_like(ck, jnp.nan)         # a read would show
    got = da.decode_attention(
        q, nan, nan, jnp.int32(1), jnp.arange(8, dtype=jnp.int32),
        jnp.zeros(8, bool), s_active=128, scale=0.25)
    assert (np.asarray(got, np.float32) == 0).all()


def test_xla_path_for_a_cache_mosaic_cannot_tile_agrees_too():
    """On a TPU, kv heads that do not fill a sublane tile (the default
    preset's 6) are attended by XLA: same keys, same zeros."""
    import jax.numpy as jnp

    from ray_tpu.ops import decode_attention as da

    assert da._tiles(8, 128) and da._tiles(16, 128)
    assert not da._tiles(6, 128) and not da._tiles(8, 64)
    shape = _SHAPES["layer_2_of_a_stack"]
    _L, B, _S, _hq, _hkv, d, layer, s_active = shape
    q, ck, cv = _inputs(shape)
    lens = jnp.asarray(_lengths(32, s_active))
    active = jnp.asarray([True, True, False, True] * 2)
    n = jnp.where(active, jnp.minimum(lens + 1, s_active), 0)
    got = da._xla_decode_attention(q, ck, cv, jnp.int32(layer), n,
                                   s_active, d ** -0.5)
    want = _reference(q, ck, cv, layer, lens, active, s_active)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("heads,kv_heads,head_dim,stored,path", [
    (10, 5, 64, (5, 64), "xla"),      # an odd number of heads of 64
    (32, 8, 64, (4, 128), "kernel"),  # cells 9 and 6: two heads a row
    (6, 6, 128, (6, 128), "xla"),     # the default preset: 6 by position
    (4, 2, 96, (2, 96), "xla"),       # neither 64 nor whole lanes
])
def test_which_heads_are_paired_and_which_still_go_to_xla(
        monkeypatch, heads, kv_heads, head_dim, stored, path):
    """Read off the config's shapes alone: an even number of heads of 64
    lies two a row and goes through the kernel on a TPU; what Mosaic still
    cannot read keeps its layout by position and XLA's attention -- and
    still decodes, to the tokens the interpreted kernel gives."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_serve
    from ray_tpu.models.llama import LlamaConfig

    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    cfg = LlamaConfig.debug(n_layers=2, n_heads=heads, n_kv_heads=kv_heads,
                            head_dim=head_dim, max_seq_len=64,
                            dtype=jnp.float32)
    params = llama.init_params(jax.random.key(3), cfg, jnp.float32)
    prompt = np.arange(1, 10, dtype=np.int32)[None]

    def served(on_a_tpu):
        monkeypatch.setattr(flash, "_use_interpret", lambda: not on_a_tpu)
        cache = llama_serve.init_cache(cfg, 2, 64)
        said = llama_serve.kv_rows(cfg, cache)
        if said["decode_attention"] == "kernel" and on_a_tpu:
            return said, None           # Mosaic: not on this backend
        cache, first, _ = llama_serve.build_prefill(cfg)(
            params, cache, jnp.asarray(prompt), jnp.asarray([9]),
            jnp.asarray([1]))
        tok = jnp.zeros(2, jnp.int32).at[1].set(first[0])
        lens = jnp.zeros(2, jnp.int32).at[1].set(9)
        zeros, no = jnp.zeros(2, jnp.int32), jnp.zeros(2, bool)
        cache, out, *_ = llama_serve.build_decode_k(cfg)(
            params, cache, tok, lens, zeros, zeros, no,
            jnp.asarray([False, True]), k=6, s_active=64)
        # the second layer's rows of the decoded positions were computed
        # from the first layer's attention
        kv = np.asarray(cache["k"]).reshape(2, 2, 64, kv_heads, head_dim)
        return said, (np.asarray(out)[:, 1], kv[1, 1, 9:15])

    said, on_chip = served(on_a_tpu=True)
    assert said == {"kv_row_heads": stored[0], "kv_row_dim": stored[1],
                    "decode_attention": path}
    assert (cfg.kv_row_heads, cfg.kv_row_dim) == stored
    interpreted, (tokens, rows) = served(on_a_tpu=False)
    assert interpreted == {**said, "decode_attention": "kernel"}
    assert np.abs(rows).min(axis=(1, 2)).all()      # all six were written
    if path == "xla":
        np.testing.assert_array_equal(on_chip[0], tokens)
        np.testing.assert_allclose(on_chip[1], rows, atol=1e-5, rtol=1e-5)


def test_block_follows_from_the_bytes_of_a_position():
    from ray_tpu.ops.decode_attention import block_k

    assert block_k(512, 8, 128, 2) == 128       # GQA 16/8 x 128, bf16
    assert block_k(512, 16, 128, 2) == 64       # MHA 16/16 x 128
    assert block_k(1280, 8, 128, 2) == 128
    assert block_k(64, 2, 16, 2) == 64          # a toy: the whole cache
    assert block_k(512, 8, 128, 4) == 64        # a float32 cache


def test_the_block_is_the_power_of_two_nearest_to_its_bytes():
    """Where the bytes of a position give a power of two the block is what
    it was (cells 3, 4 and 5: PR 29's fit; cells 7 and 10); between two,
    the nearer and not the lower: cell 11's 102 positions of 10 rows are
    128, whose DMA outlasts the block's chain, where 64 read at the
    chain's pace (PERF.md section 6, PR 52)."""
    from ray_tpu.ops.decode_attention import block_k

    assert block_k(512, 8, 128, 2) == 128         # cell 3
    assert block_k(1280, 8, 128, 2) == 128        # cell 4
    assert block_k(512, 16, 128, 2) == 64         # cell 5
    assert block_k(16384, 4, 128, 2) == 256       # cells 7 and 10
    assert block_k(4096, 4, 128, 2) == 256        # cell 7's rings
    assert block_k(16384, 10, 128, 2) == 128      # cell 11's pool: 102
    assert block_k(512, 10, 128, 2) == 128        # and its rings
    assert block_k(16384, 12, 128, 2) == 64       # 85: the lower is nearer
    assert block_k(16384, 6, 128, 2) == 128       # 170
    assert block_k(16384, 5, 128, 2) == 256       # 204
    assert block_k(16384, 10, 128, 4) == 64       # 51, a float32 cache
    assert block_k(100, 10, 128, 2) == 100        # the whole cache


def test_the_benchmarks_cells_take_the_block_their_shapes_say():
    """``block_k`` sees four numbers, none of them a model's name, and the
    benchmark's cells that run this kernel take the block their shapes
    say -- read from the configuration files as the benchmark builds its
    programs."""
    import inspect
    import json
    import os

    import jax.numpy as jnp

    from benchmarks.lib import program
    from ray_tpu.ops import decode_attention as da

    assert list(inspect.signature(da.block_k).parameters) == \
        ["s", "hkv", "d", "itemsize"]
    root = os.path.join(os.path.dirname(__file__), os.pardir)

    def load(*path):
        with open(os.path.join(root, *path)) as f:
            return json.load(f)

    taken = {}
    for entry in load("BENCHMARK.json")["workloads"]:
        cell = load("benchmarks", "workloads", entry["name"] + ".json")
        if "engine" not in cell:
            continue                                  # a train cell
        cfg = program.llama_config(
            load("benchmarks", "configs", entry["config"] + ".json"))
        hkv, d = cfg.kv_row_heads, cfg.kv_row_dim
        if cfg.kv_lora_rank or not da._tiles(hkv, d, as_rows=True):
            continue            # latent attention's own kernel; XLA's
        taken[entry["name"]] = (
            cfg.n_heads, hkv, d,
            da.block_k(cell["engine"]["max_len"], hkv, d,
                       jnp.dtype(cfg.dtype).itemsize))
    assert taken.items() >= {
        "internlm2-1.8b.serve-batch-decode": (16, 8, 128, 128),
        "internlm2-1.8b.serve-chat-busy": (16, 8, 128, 128),
        "olmoe-1b-7b.serve-batch-decode": (16, 16, 128, 64),
        "smallthinker-21b-a3b.serve-long-prompt": (28, 4, 128, 256),
        "keye-vl-2.0-30b-a3b.serve-long-prompt": (32, 4, 128, 256),
        "phi-4-mini-flash-reasoning.serve-long-prompt": (40, 10, 128, 128),
        # GQA 32/8 x 64, two heads a row: cells 7's and 10's geometry
        "granite-4.0-h-micro.serve-batch-decode": (32, 4, 128, 256),
        "lfm2-8b-a1b.serve-batch-decode-wide": (32, 4, 128, 256),
    }.items()


def test_interpret_is_asked_of_flash_attention_at_call_time(monkeypatch):
    """The benchmark's compile-for-a-described-chip tests steer kernels
    to Mosaic by replacing ``flash_attention._use_interpret``."""
    import jax.numpy as jnp

    from ray_tpu.ops import decode_attention as da

    flash = importlib.import_module("ray_tpu.ops.flash_attention")

    class Asked(Exception):
        pass

    def asked():
        raise Asked

    monkeypatch.setattr(flash, "_use_interpret", asked)
    q, ck, cv = _inputs(_SHAPES["toy_hkv2_d16"])
    with pytest.raises(Asked):
        da.decode_attention(q, ck, cv, jnp.int32(0),
                            jnp.zeros(8, jnp.int32), jnp.ones(8, bool),
                            s_active=128, scale=0.25)

"""The serving flash forward told its rows' lengths
(``flash_prefill_attention(..., lengths=)``): a q block that lies wholly
past its row's length runs no tile and leaves as exact zeros, and every
position before the length is what the kernel without ``lengths`` gives,
bit for bit -- alone, with a band, with a learned mask, with and without
the softmax statistics; ``prefill_with_states`` hands its ``lengths`` on
for the latent, the windowed and the indexed model alike and returns what
it returned; ``serve.prefill_group`` counts the declined blocks.

Kernels interpreted, tiles of 16 so that a row of 64 has four q blocks.
"""

import asyncio
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from ray_tpu.models import indexer, llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import timeline

flash = importlib.import_module("ray_tpu.ops.flash_attention")

S, BLOCK = 64, 16
# a padding row; an end in the first block, inside a block, on a block's
# edge; the full sequence
LENGTHS = (0, 5, 37, 48, 64)


@pytest.fixture
def small_tiles(monkeypatch):
    monkeypatch.setattr(flash, "DEFAULT_BLOCK", BLOCK)


def _qkv(seed, heads, kv_heads, d, dv):
    keys = jax.random.split(jax.random.key(seed), 3)
    B = len(LENGTHS)
    return (jax.random.normal(keys[0], (B, S, heads, d), jnp.bfloat16),
            jax.random.normal(keys[1], (B, S, kv_heads, d), jnp.bfloat16),
            jax.random.normal(keys[2], (B, S, kv_heads, dv), jnp.bfloat16))


def _keep(seed):
    """A selection below the diagonal, every query seeing its own key."""
    rng = np.random.default_rng(seed)
    keep = rng.random((len(LENGTHS), S, S)) < 0.4
    keep |= np.eye(S, dtype=bool)[None]
    keep[1, 20:, :16] = False            # a tile with nothing in it
    return jnp.asarray(keep, jnp.int8)


@pytest.mark.parametrize("lse", [True, False], ids=["lse", "no_lse"])
@pytest.mark.parametrize("heads,kv_heads,d,dv", [
    (2, 2, 192, 128),      # latent attention's expanded head
    (4, 2, 128, 128),      # grouped queries
], ids=["192_128", "gqa_128"])
@pytest.mark.parametrize("kind", ["plain", "window", "keep"])
def test_rows_before_the_length_are_the_kernels_without_lengths(
        small_tiles, kind, heads, kv_heads, d, dv, lse):
    q, k, v = _qkv(len(kind) + heads, heads, kv_heads, d, dv)
    kw = {"plain": {}, "window": {"window": 24},
          "keep": {"keep": _keep(heads)}}[kind]
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    want = np.asarray(flash.flash_prefill_attention(
        q, k, v, scale=d ** -0.5, lse=lse, **kw).astype(jnp.float32))
    got = np.asarray(flash.flash_prefill_attention(
        q, k, v, scale=d ** -0.5, lse=lse, lengths=lengths,
        **kw).astype(jnp.float32))
    assert np.isfinite(got).all()
    for row, n in enumerate(LENGTHS):
        run = BLOCK * flash.q_blocks_run(S, n)[1]
        assert run == -(-n // BLOCK) * BLOCK
        assert np.array_equal(got[row, :run], want[row, :run]), (kind, n)
        assert not got[row, run:].any(), (kind, n)
    if not lse:
        return
    # the statistics of the same calls: a declined block's read NEG_INF,
    # an all-masked row's, and the others are the kernel's own
    t = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    call = dict(causal=True, block_q=None, block_k=None, interpret=True, **kw)
    _, want_lse = flash._fwd(t(q), t(k), t(v), **call)
    o, got_lse = flash._fwd(t(q), t(k), t(v), lengths=lengths, **call)
    assert np.array_equal(np.asarray(t(o).astype(jnp.float32)),
                          np.asarray(flash.flash_prefill_attention(
                              q, k, v, scale=1.0, lengths=lengths,
                              **kw).astype(jnp.float32)))
    want_lse, got_lse = (np.asarray(x).reshape(len(LENGTHS), heads, S)
                         for x in (want_lse, got_lse))
    for row, n in enumerate(LENGTHS):
        run = BLOCK * flash.q_blocks_run(S, n)[1]
        assert np.array_equal(got_lse[row, :, :run], want_lse[row, :, :run])
        assert (got_lse[row, :, run:] == flash.NEG_INF).all()


@pytest.mark.parametrize("seq,block,length,want", [
    (64, 16, None, 10 / 16),       # the bucket's causal tiles: 4 + 3 + 2 + 1
    (64, 16, 64, 10 / 16),
    (64, 16, 48, 6 / 16),          # on a block's edge: that block is declined
    (64, 16, 37, 6 / 16),          # inside the third block: it runs whole
    (64, 16, 5, 1 / 16),
    (64, 16, 0, 0.0),
    (12288, None, 6144, 21 / 144),
])
def test_the_share_of_a_buckets_square_a_row_of_a_length_computes(
        seq, block, length, want):
    assert flash.causal_computed_share(
        seq, block, block, strip=block or flash.DEFAULT_BLOCK,
        length=length) == pytest.approx(want)


# ------------------------------------------------- prefill_with_states
def _latent():
    return LlamaConfig(
        vocab_size=256, hidden_size=64, n_layers=2, n_heads=4, n_kv_heads=4,
        head_dim=24, intermediate_size=128, max_seq_len=S, norm_eps=1e-6,
        tie_embeddings=False, remat=False, dtype=jnp.float32,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, first_dense_layers=1,
        moe_experts=8, moe_top_k=2, moe_norm_topk=False,
        moe_intermediate_size=32, moe_shared_size=64)


def _windowed():
    return LlamaConfig.debug(
        vocab_size=256, hidden_size=64, n_layers=4, n_heads=8, n_kv_heads=4,
        head_dim=16, intermediate_size=32, moe_experts=8, moe_top_k=3,
        moe_norm_topk=True, moe_router_input="layer", moe_activation="relu",
        window_size=24, layer_pattern=("attention", "window"),
        nope_kinds=("attention",), tie_embeddings=False, max_seq_len=S,
        dtype=jnp.float32)


def _indexed():
    return LlamaConfig(
        vocab_size=256, hidden_size=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, intermediate_size=128, max_seq_len=S, rope_theta=1e7,
        norm_eps=1e-6, tie_embeddings=False, remat=False, dtype=jnp.float32,
        qk_head_norm=True, moe_experts=8, moe_top_k=2, moe_norm_topk=True,
        moe_intermediate_size=32, index_heads=4, index_head_dim=8,
        index_topk=8)


def _by_position(leaf, axis):
    """A cache leaf ``(L, G, ...)`` with its positions on ``axis`` ->
    ``(G, P, ...)`` numpy, so that ``[row, :length]`` is a row's own."""
    return np.moveaxis(np.asarray(leaf), (1, axis), (0, 1))


@pytest.mark.parametrize("toy", [_latent, _windowed, _indexed])
def test_prefill_with_states_returns_what_it_returned(
        small_tiles, monkeypatch, toy):
    """Two prompts and a padding row in a bucket of 64 past a
    ``FLASH_PREFILL_FROM`` of 16: with the lengths handed to the kernel,
    the logits, the expert rows and every cache row before a prompt's end
    are those of the same program with the lengths held back."""
    monkeypatch.setattr(llama, "FLASH_PREFILL_FROM", 16)
    monkeypatch.setattr(llama, "LATENT_HEAD_GROUP", 2)
    monkeypatch.setattr(indexer, "QUERY_TILE", 8)
    cfg = toy()
    params = family.init_params(jax.random.key(5), cfg)
    lens = (21, 48, 0)
    tokens = np.zeros((3, S), np.int32)
    rng = np.random.default_rng(4)
    for row, n in enumerate(lens):
        tokens[row, :n] = rng.integers(1, 256, n)
    args = (params, jnp.asarray(tokens), jnp.asarray(lens, jnp.int32), cfg)
    kernel = flash.flash_prefill_attention
    told = []

    def spy(*a, lengths=None, **kw):
        told.append(lengths is not None)
        return kernel(*a, lengths=lengths, **kw)

    monkeypatch.setattr(flash, "flash_prefill_attention", spy)
    got = llama.prefill_with_states(*args)
    assert told and all(told)
    monkeypatch.setattr(flash, "flash_prefill_attention",
                        lambda *a, lengths=None, **kw: kernel(*a, **kw))
    want = llama.prefill_with_states(*args)

    logits, ks, vs, expert_rows, _states, window, index_keys = got
    assert np.array_equal(np.asarray(logits)[:2], np.asarray(want[0])[:2])
    assert np.isfinite(np.asarray(logits)).all()
    assert np.array_equal(np.asarray(expert_rows), np.asarray(want[3]))
    leaves = [(ks, want[1], 2)]
    if vs is not None:
        leaves.append((vs, want[2], 2))
    if window is not None:
        leaves += [(window[0], want[5][0], 2), (window[1], want[5][1], 2)]
    if index_keys is not None:
        leaves.append((index_keys, want[6], 3))     # (L, G, 8, P)
    for mine, theirs, axis in leaves:
        mine, theirs = _by_position(mine, axis), _by_position(theirs, axis)
        assert np.isfinite(mine).all()
        for row, n in enumerate(lens):
            assert np.array_equal(mine[row, :n], theirs[row, :n])
    assert (cfg.kv_lora_rank > 0) == (vs is None)
    assert (window is not None) == bool(cfg.window_size)
    assert (index_keys is not None) == bool(cfg.index_topk)


# ------------------------------------------------ serve.prefill_group
def test_the_prefill_span_counts_the_blocks_the_kernel_declines(
        small_tiles, monkeypatch, traced):
    """A bucket past ``FLASH_PREFILL_FROM`` carries ``flash_q_blocks`` and
    ``flash_q_blocks_declined``, the padding rows' among them, by the
    kernel's own block size; a bucket under it carries neither."""
    from ray_tpu.serve import llm

    monkeypatch.setattr(llama, "FLASH_PREFILL_FROM", 16)
    server = llm.LLMServer(model_preset="debug", max_slots=4, max_len=128,
                           prefill_buckets=(16, 64), decode_chunk=4,
                           prefill_groups=(1, 2), warmup=False)

    async def run(requests):
        return await asyncio.gather(*[server.generate(r) for r in requests])

    try:
        # one request a wave: which prompts share a padded group is then
        # no matter of timing (sent together, 9 rode 37's bucket of 64
        # whenever the two met in a wave, and declined three blocks there)
        for n in (37, 9, 64):
            asyncio.run(run([{"prompt": list(range(1, 1 + n)),
                              "max_new_tokens": 3}]))
        asyncio.run(run([{"prompt": [7], "max_new_tokens": 1}]))
    finally:
        server.shutdown()
    groups = [e["args"] for e in timeline.export_timeline()
              if e.get("ph") == "X" and e["name"] == "serve.prefill_group"]
    short = [g for g in groups if g["bucket"] == 16]
    long = [g for g in groups if g["bucket"] == 64]
    assert short and long
    assert not any(k.startswith("flash_") for g in short for k in g)
    for g in long:
        assert g["flash_q_blocks"] == g["rows_padded"] * 64 // BLOCK
    # 37 -> 3 of 4 blocks run, 64 -> all four; padding rows: none
    declined = sum(g["flash_q_blocks_declined"] for g in long)
    padding = sum(g["rows_padded"] - g["rows"] for g in long)
    assert declined == 1 + 0 + 4 * padding

"""Keye-VL-2.0-30B-A3B's language model at toy widths through the dense
serving plane, held to ``benchmarks/references/keye_sparse_decoder.py``
(float32, no cache, no index-key pool, the selection a sort of the causal
row):

- prefill (rows on both sides of the toy ``topk`` in one padded group),
  then decode through K/V and the index keys, against the reference's full
  forward pass at every position; the sets the engine selects, in the
  prefill and in every decode step, are the reference's;
- a row no longer than ``topk`` is the same model without an indexer;
- prefill by buckets of different widths gives the same index keys and
  logits;
- exact top-k with ties (a mask by bisection) against a stable sort; the
  flash forward and the decode kernel with a mask that is data, over
  several tiles and blocks;
- the broken variants of ``benchmarks/tools/dsa_check.py`` each FAIL;
- a reused slot, an inactive slot and a slot past ``s_active`` neither
  write nor read a foreign index key;
- the parameter and cache trees, config refusals, the planes that refuse
  the model, training refused;
- ``LLMServer.generate`` end to end, spans / counters / the index-key pool.
"""

import contextlib
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.references import keye_sparse_decoder as reference
from benchmarks.tools import dsa_check
from ray_tpu.models import indexer, llama, llama_serve
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import metrics, timeline, tracing

VOCAB, SLOTS, MAX_LEN, TOPK = 256, 4, 64, 8
TOL = 1e-3          # float32 both sides: the order of sums alone


def _cfg(**kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=64, n_layers=2, n_heads=4,
        n_kv_heads=2, head_dim=16, intermediate_size=128,
        max_seq_len=MAX_LEN, rope_theta=1e7, norm_eps=1e-6,
        tie_embeddings=False, remat=False, dtype=jnp.float32,
        qk_head_norm=True, moe_experts=8, moe_top_k=2, moe_norm_topk=True,
        moe_intermediate_size=32, index_heads=4, index_head_dim=8,
        index_topk=TOPK)
    base.update(kw)
    return LlamaConfig(**base)


def _published(cfg):
    """The toy configuration in the published key names (what the
    reference reads)."""
    return {"num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "hidden_size": cfg.hidden_size, "rope_theta": cfg.rope_theta,
            "rms_norm_eps": cfg.norm_eps, "vocab_size": cfg.vocab_size,
            "num_experts": cfg.moe_experts,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_norm_topk,
            "tie_word_embeddings": False, "attention_bias": False,
            "mlp_only_layers": [], "decoder_sparse_step": 1,
            "rope_scaling": {"mrope_section": [2, 3, 3],
                             "rope_type": "default", "type": "default"},
            "sa_config": {"indexer_head_dim": cfg.index_head_dim,
                          "indexer_num_heads": cfg.index_heads,
                          "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                          "q_chunk_size": 512, "topk": cfg.index_topk}}


def _init(cfg, seed=11):
    """The program's own weights with the norms moved off 1 (where a norm
    sits and which weight it takes is then seen) and the index projections
    five times as wide, so that the index scores spread and the selection
    is far from any tie."""
    def init(key, moving):
        keys = iter(jax.random.split(moving, 64))

        def moved(path, x):
            name = path[-1].key
            if name.endswith("norm"):
                return x * (1 + 0.2 * jax.random.normal(next(keys), x.shape))
            return 5 * x if name in indexer.LEAVES else x

        return jax.tree_util.tree_map_with_path(
            moved, llama.init_params(key, cfg))

    # one program: op by op the initialiser is a hundred small compiles
    return jax.jit(init)(jax.random.key(seed), jax.random.key(seed + 1))


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, _init(cfg), _published(cfg)


def _gap(params, prompt, emitted, published, pad_to=0):
    """The RAW gaps' largest: in float32 no tie breaks the other way, and
    nothing is taken out by count (``take_out_undecided`` is the chip's)."""
    return float(reference.teacher_forced_report(
        params, prompt, emitted, published, pad_to)["gap"].max())


# ----------------------------------------------- engine against reference
def test_prefill_then_decode_through_kv_and_index_keys(model):
    """Three prompts in ONE padded group -- 3 and 7 tokens (no query
    selects: under ``topk`` 8), 30 (every query from the ninth on selects)
    -- then decoded together through both sides of ``topk``, one of them
    sitting out a chunk: every emitted position of each within TOL, and
    every set a decode step selected is the reference's."""
    cfg, params, published = model
    rng = np.random.default_rng(3)
    lengths = (3, 7, 30)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32) for n in lengths]
    slots = (2, 0, 3)
    cache = llama_serve.init_cache(cfg, SLOTS, MAX_LEN)
    cache, first, load = family.prefill(cfg, params, cache, prompts, slots)
    assert (np.asarray(load[0]).sum(1) == 40 * cfg.moe_top_k).all()
    tok, lens = family.seat(first, lengths, slots)
    emitted = {s: [int(t)] for s, t in zip(slots, first)}
    chosen = {s: [] for s in slots}
    with contextlib.ExitStack() as recorders:
        for s in slots:
            recorders.enter_context(
                dsa_check.recorded_selection(s, chosen[s]))
        family.forget_programs()        # traced under the recorders
        for who in (slots, slots, (2, 3), slots, slots):
            cache, out, tok, lens, load = family.decode(
                cfg, params, cache, tok, lens, who)
            for s in who:
                emitted[s] += [int(t) for t in out[:, s]]
        jax.effects_barrier()
    family.forget_programs()
    # (a slot that sat out a chunk recorded empty sets there)
    chosen = {s: [c for c in sets if len(c)] for s, sets in chosen.items()}
    assert [len(emitted[s]) for s in slots] == [21, 17, 21]
    for prompt, s in zip(prompts, slots):
        assert _gap(params, prompt, emitted[s], published) <= TOL
        steps = len(emitted[s]) - 1
        got = dsa_check.overlap(reference, params, prompt, emitted[s],
                                published, chosen[s], cfg.n_layers, steps)
        assert got["sets"] == steps * cfg.n_layers
        assert got["keys_not_in_reference_max"] == 0
        assert got["set_size_max"] == min(TOPK, len(prompt) + steps)
    # the longest row selected in every step, the shortest in the last few
    assert all(len(c) == TOPK for c in chosen[3])
    assert len(chosen[2][0]) == 4 and len(chosen[2][-1]) == TOPK


def test_prefill_logits_and_selected_sets_are_the_references(model):
    """One row's logits at eight of its positions (a padded group of
    eight rows of unlike lengths, both sides of ``topk``), and the mask
    each layer's prefill made against the reference's selected sets."""
    cfg, params, published = model
    rng = np.random.default_rng(4)
    row = rng.integers(0, VOCAB, 32).astype(np.int32)
    lengths = np.asarray([1, 4, 8, 9, 12, 19, 27, 32], np.int32)
    want = reference.logits(params, row[None], published)[0]
    masks, keep = [], indexer.prefill_keep

    def recording(qi, ki_t, w, k, lengths=None):
        out = keep(qi, ki_t, w, k, lengths)
        jax.debug.callback(lambda m: masks.append(np.asarray(m[-1])), out,
                           ordered=True)
        return out

    indexer.prefill_keep = recording
    try:
        got = llama.prefill_with_states(
            params, jnp.asarray(np.tile(row, (8, 1))), jnp.asarray(lengths),
            cfg)
        jax.effects_barrier()
    finally:
        indexer.prefill_keep = keep
    for g, n in enumerate(lengths):
        assert float(jnp.abs(got[0][g] - want[n - 1]).max()) <= TOL
    assert got[6].shape == (cfg.n_layers, 8, cfg.index_head_dim, 32)
    selected = reference.selected_keys(params, row, published)
    assert selected.shape == (cfg.n_layers, 32, 32)
    assert len(masks) == cfg.n_layers
    for layer, mask in enumerate(masks):
        assert ((mask != 0) == selected[layer]).all()
    assert selected[0].sum(1).tolist() == [min(t + 1, TOPK)
                                           for t in range(32)]


def test_a_row_no_longer_than_topk_is_the_model_without_an_indexer(model):
    """Prompts that, decoded, stay within ``topk`` keys: the first logits
    and every token are those of the same weights without an indexer --
    the prefill's exactly (the same program but for the projections), the
    decode's through the same kernel with nothing masked."""
    cfg, params, _published = model
    plain = dataclasses.replace(cfg, index_heads=0, index_head_dim=0,
                                index_topk=0)
    plain_params = {**params, "layers": {
        k: v for k, v in params["layers"].items()
        if k not in indexer.LEAVES}}
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32) for n in (2, 4)]
    toks = np.zeros((2, 16), np.int32)
    for g, p in enumerate(prompts):
        toks[g, :len(p)] = p
    lengths = jnp.asarray([2, 4], jnp.int32)
    a = llama.prefill_with_states(params, jnp.asarray(toks), lengths, cfg)
    b = llama.prefill_with_states(plain_params, jnp.asarray(toks), lengths,
                                  plain)
    assert (np.asarray(a[0]) == np.asarray(b[0])).all()
    assert b[6] is None
    emitted = []
    for c, p in ((cfg, params), (plain, plain_params)):
        cache = llama_serve.init_cache(c, SLOTS, MAX_LEN)
        cache, first, _ = family.prefill(c, p, cache, prompts, (1, 3),
                                         bucket=16)
        tok, lens = family.seat(first, (2, 4), (1, 3))
        cache, out, tok, lens, _ = family.decode(c, p, cache, tok, lens,
                                                 (1, 3))
        emitted.append(np.concatenate([first[None], out[:, [1, 3]]]))
    assert (emitted[0] == emitted[1]).all()


def test_buckets_of_different_widths_give_the_same_keys_and_logits(model):
    cfg, params, _published = model
    prompt = np.random.default_rng(6).integers(0, VOCAB, 13).astype(np.int32)
    got = []
    for bucket in (16, 64):
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :13] = prompt
        out = llama.prefill_with_states(
            params, jnp.asarray(toks), jnp.asarray([13], jnp.int32), cfg)
        got.append((np.asarray(out[0]), np.asarray(out[6])[..., :13],
                    np.asarray(out[1])[:, :, :13]))
    for logits, index_keys, ks in got[1:]:
        assert np.abs(logits - got[0][0]).max() <= 1e-5
        assert np.abs(index_keys - got[0][1]).max() <= 2e-5
        assert np.abs(ks - got[0][2]).max() <= 1e-5


# ------------------------------------------------------- exact selection
def _stable_topk(x, k):
    order = np.argsort(-x, axis=-1, kind="stable")[..., :k]
    want = np.zeros(x.shape, bool)
    np.put_along_axis(want, order, True, -1)
    return want & (x > -np.inf)


@pytest.mark.parametrize("k", [1, 8, 33, 100])
def test_exact_topk_with_ties(k):
    """Rows of equal scores (a plateau at the k-th place, zeros of both
    signs, a row with fewer candidates than k) against a stable sort, a
    prefill's tile of rows and a decode step's one query a row."""
    rng = np.random.default_rng(k)
    x = rng.normal(size=(3, 7, 64)).astype(np.float32)
    x[0, 0, :40] = 0.5
    x[0, 1, ::2] = 0.0
    x[0, 1, 1::4] = -0.0
    x[0, 2] = np.round(x[0, 2], 1)
    x[1, 2, 10:] = -np.inf
    x[2, 3] = 1.0
    want = _stable_topk(np.where(x == 0, 0.0, x), k)
    got = np.asarray(indexer.topk_keep(
        jnp.where(jnp.asarray(x) == 0, 0.0, jnp.asarray(x)), k))
    assert (got == want).all()
    # a decode step's: one query a row, candidates the first n_valid
    rows = x.reshape(21, 64)
    rows = np.where(np.isinf(rows), -1.0, rows)
    n_valid = rng.integers(0, 65, 21).astype(np.int32)
    rows = np.where(rows == 0, 0.0, rows)
    keep = np.asarray(indexer.select(jnp.asarray(rows),
                                     jnp.asarray(n_valid), k))
    for r in range(21):
        masked = np.where(np.arange(64) < n_valid[r], rows[r], -np.inf)
        assert (keep[r] == _stable_topk(masked, k)).all()
        assert keep[r].sum() == min(n_valid[r], k)


def test_the_decode_kernel_takes_a_selection(monkeypatch):
    """Rows of four blocks: one that attends nothing of its first two
    blocks, one whose newest key is masked, an inactive one; against the
    masked einsum."""
    from ray_tpu.ops import decode_attention as kernel

    monkeypatch.setattr(kernel, "_BLOCK_BYTES", 16 * 2 * 128 * 4)
    B, S, H, Hkv, D, L = 4, 64, 4, 2, 128, 2
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    ck, cv = (jnp.asarray(rng.normal(size=(L, B, S * Hkv, D)), jnp.float32)
              for _ in range(2))
    assert kernel.block_k(S, Hkv, D, 4) == 16
    lens = jnp.asarray([63, 40, 17, 50], jnp.int32)
    active = jnp.asarray([True, True, True, False])
    keep = rng.random((B, S)) < 0.4
    keep[0, :32] = False
    keep[0, 33] = True
    keep[1, 40] = False
    keep[:, 0] |= np.asarray([False, True, True, True])
    got = kernel.decode_attention(q, ck, cv, jnp.int32(1), lens, active,
                                  s_active=S, scale=D ** -0.5, hkv=Hkv,
                                  keep=jnp.asarray(keep))
    k5, v5 = (c[1].reshape(B, S, Hkv, D) for c in (ck, cv))
    seen = keep & (np.arange(S)[None, :] <= np.asarray(lens)[:, None])
    want = llama._cache_attend(q[:, None], k5, v5, lens[:, None], D ** -0.5,
                               jnp.broadcast_to(jnp.arange(S), (B, S)),
                               jnp.asarray(seen))[:, 0]
    assert float(jnp.abs(got - want)[:3].max()) < 1e-5
    assert (np.asarray(got)[3] == 0).all()
    # with every key kept: the kernel without a selection, bit for bit
    dense = kernel.decode_attention(q, ck, cv, jnp.int32(1), lens, active,
                                    s_active=S, scale=D ** -0.5, hkv=Hkv)
    every = kernel.decode_attention(
        q, ck, cv, jnp.int32(1), lens, active, s_active=S, scale=D ** -0.5,
        hkv=Hkv, keep=jnp.ones((B, S), bool))
    assert (np.asarray(dense) == np.asarray(every)).all()


def test_the_flash_forward_takes_a_mask_that_is_data(monkeypatch):
    """Four tiles a side: rows that see nothing of a tile before their
    first key, rows whose own key is masked."""
    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "DEFAULT_BLOCK", 128)
    B, S, H, Hkv, D = 2, 512, 4, 2, 128
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(B, S, h, D)), jnp.float32)
               for h in (H, Hkv, Hkv))
    keep = rng.random((B, S, S)) < 0.1
    keep[:, :, 300:] |= rng.random((B, S, S - 300)) < 0.5
    keep[:, np.arange(S), np.arange(S)] = False
    keep[:, :, 0] = True                  # every row sees a key
    keep[0, 400:, :384] = False           # nothing in its first three tiles
    keep[0, 400:, 390] = True
    keep = jnp.asarray(keep, jnp.int8)
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    want = llama.dot_attention(q, k, v, pos, D ** -0.5, None, keep)
    for lse in (True, False):
        got = flash.flash_prefill_attention(q, k, v, scale=D ** -0.5,
                                            keep=keep, lse=lse)
        assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("engaged", [False, True])
def test_a_long_prefill_selects_through_the_flash_forward(model, monkeypatch,
                                                          engaged):
    """Past ``FLASH_PREFILL_FROM`` the mask goes to the kernel, and the
    scores are made a query tile at a time: by XLA's form at the toy index
    width, and at one that engages (index heads of 64, a row of two whole
    tiles of 512 that ends inside the second) by ``ops/index_select.py``,
    told the row's length."""
    from ray_tpu.ops import index_select

    cfg, params, published = model
    monkeypatch.setattr(llama, "FLASH_PREFILL_FROM", 16)
    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    if engaged:
        bucket, length = 1024, 900
        wide = dataclasses.replace(cfg, head_dim=128, index_head_dim=64,
                                   max_seq_len=bucket)
    else:
        bucket, length = 32, 29
        monkeypatch.setattr(indexer, "QUERY_TILE", 8)
        monkeypatch.setattr(flash, "DEFAULT_BLOCK", 16)
        wide = dataclasses.replace(cfg, head_dim=128)
    assert engaged == index_select.engages(
        1, bucket, wide.index_topk, wide.index_heads, wide.index_head_dim,
        indexer.QUERY_TILE)
    calls, kernel = [], index_select.prefill_keep

    def counting(qi, keys_t, w, lengths, k, tile):
        calls.append((qi.shape[1], k, tile))
        return kernel(qi, keys_t, w, lengths, k, tile)

    monkeypatch.setattr(index_select, "prefill_keep", counting)
    params = _init(wide)
    row = np.random.default_rng(8).integers(0, VOCAB, bucket).astype(np.int32)
    got = llama.prefill_with_states(
        params, jnp.asarray(row[None]), jnp.asarray([length], jnp.int32),
        wide)
    want = reference.logits(params, row[None], _published(wide))[0]
    assert float(jnp.abs(got[0][0] - want[length - 1]).max()) <= TOL
    # traced once: the layers are one scan
    assert calls == [(bucket, TOPK, 512)] * engaged


# ------------------------------------------------------- broken programs
@pytest.mark.parametrize("variant", dsa_check.VARIANTS)
def test_a_broken_variant_fails_the_reference(model, variant):
    """The same weights under a program that is wrong in one place
    (``benchmarks/tools/dsa_check.py`` runs the same variants at the
    published widths on the chip): over the margin, where the intact
    program reads under 0.001."""
    cfg, params, published = model
    rng = np.random.default_rng(0)
    before, prompt = (rng.integers(0, VOCAB, n).astype(np.int32)
                      for n in (14, 21))
    vcfg, patched, weights = dsa_check.broken(variant, cfg, MAX_LEN)
    with patched():
        emitted = dsa_check.serve_one(vcfg, weights(params), before, prompt,
                                      24, (16, 32), MAX_LEN, k=4, slots=3)
    family.reads_as(_gap(params, prompt, emitted, published), variant, TOL)


# ----------------------------------------------------------------- slots
def test_no_slot_writes_or_reads_a_foreign_index_key(model):
    """A slot that is not in the launch and a slot that has run past the
    attended prefix write no index key (nor K/V); a request served in a
    slot another held before it (longer, and decoded there) emits what it
    emits in a fresh cache."""
    cfg, params, _published = model
    rng = np.random.default_rng(9)
    long, short = (rng.integers(0, VOCAB, n).astype(np.int32)
                   for n in (40, 12))
    cache = llama_serve.init_cache(cfg, SLOTS, MAX_LEN)
    cache, first, _ = family.prefill(cfg, params, cache, [long, short],
                                     (1, 2), bucket=64)
    tok, lens = family.seat(first, (40, 12), (1, 2))
    held = jax.tree.map(np.asarray, cache)
    # slot 1 sits out; slot 2 decodes under an attended prefix of 32
    cache, _out, tok, lens, _ = family.decode(cfg, params, cache, tok, lens,
                                              (2,), s_active=32)
    for name in ("k", "v", "ik"):
        assert (np.asarray(cache[name])[:, 1] == held[name][:, 1]).all()
        assert (np.asarray(cache[name])[:, 0] == 0).all()
    assert (np.asarray(cache["ik"])[:, 2, :, 12:16] != 0).any()
    # slot 1 at 40 positions is past a prefix of 32: in the launch, it
    # writes nothing
    now = jax.tree.map(np.asarray, cache)
    cache, _out, tok, lens, _ = family.decode(cfg, params, cache, tok, lens,
                                              (1, 2), s_active=32)
    for name in ("k", "v", "ik"):
        assert (np.asarray(cache[name])[:, 1] == now[name][:, 1]).all()

    def reply(cache, slot):
        cache, first, _ = family.prefill(cfg, params, cache, [short],
                                         (slot,), bucket=16)
        tok, lens = family.seat(first, (12,), (slot,))
        out = [first]
        for _ in range(3):
            cache, toks, tok, lens, _ = family.decode(
                cfg, params, cache, tok, lens, (slot,))
            out.append(toks[:, slot])
        return np.concatenate(out)

    fresh = reply(llama_serve.init_cache(cfg, SLOTS, MAX_LEN), 1)
    assert (reply(cache, 1) == fresh).all()      # after ``long`` held it


# ------------------------------------------------ trees, config, refusals
def test_the_parameter_and_cache_trees():
    cfg = _cfg()
    params = jax.eval_shape(lambda k: llama.init_params(k, cfg),
                            jax.random.key(0))
    layers = {k: v.shape for k, v in params["layers"].items()}
    assert layers["wq_idx"] == (2, 64, 4 * 8)
    assert layers["wk_idx"] == (2, 64, 8)
    assert layers["ww_idx"] == (2, 64, 4)
    axes = llama.param_logical_axes(cfg)
    assert jax.tree.structure(
        axes, is_leaf=lambda v: isinstance(v, tuple)) == jax.tree.structure(
        params)
    cache = jax.eval_shape(lambda: llama_serve.init_cache(cfg, 4, 64))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, 4, 64 * 2, 16), "v": (2, 4, 64 * 2, 16),
        "ik": (2, 4, 8, 64)}
    assert llama_serve.cache_pools(cfg, 4, 64) == {
        "kv": (2 * 2 * 4 * 64 * 2 * 16 * 4, "float32"),
        "index_keys": (2 * 4 * 8 * 64 * 4, "float32")}
    assert llama_serve.state_bytes_per_slot(cfg) == {}
    without = family.init_params(jax.random.key(0), _cfg(
        index_heads=0, index_head_dim=0, index_topk=0))
    assert not set(indexer.LEAVES) & set(without["layers"])


@pytest.mark.parametrize("kw,words", [
    (dict(layer_pattern=("attention", "window"), window_size=8,
          n_layers=4), "beside window rings"),
    (dict(kv_lora_rank=32, q_lora_rank=16, qk_nope_head_dim=8,
          qk_rope_head_dim=8, v_head_dim=16, qk_head_norm=False),
     "a latent"),
    (dict(first_dense_layers=1), "one stack"),
    (dict(index_heads=0), "index_heads"),
])
def test_config_refusals(kw, words):
    with pytest.raises(ValueError, match=words):
        _cfg(**kw)


def test_training_and_the_one_stack_cache_refuse_the_config(model):
    cfg, params, _published = model
    assert not cfg.plain_decoder and not cfg.one_kv_stack
    toks = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match="served only"):
        llama.forward(params, toks, cfg)
    with pytest.raises(NotImplementedError, match="one K/V stack alone"):
        llama.forward_with_cache(params, toks[:, :1], toks[:, :1], {}, cfg)


_presets = family.presets({
    "keye_debug_f32": _cfg,
    "keye_debug": lambda **kw: _cfg(**{"dtype": jnp.bfloat16, **kw}),
    # index heads of 64: a row of whole tiles engages ops/index_select.py
    "keye_debug_wide": lambda **kw: _cfg(**{"index_head_dim": 64, **kw})})
engine = family.engines("keye_debug", max_len=128)


@pytest.mark.parametrize("plane,args", family.PLANES)
def test_planes_that_hold_no_index_keys_refuse_the_config(plane, args):
    """Blocks, shared prefixes, a rejected draft's rewind, a K/V hand-off
    and K/V quantization hold K and V rows alone."""
    family.refuses_plane("keye_debug", plane, args, "has an indexer",
                         words=("no index-key pool",), absent=("window",))


@pytest.mark.parametrize("preset,form,groups", [
    # (bucket, a row's lengths) -> (tiles a layer, declined)
    ("keye_debug_wide", "kernel", [
        ((1536, [700]), (3, 1)), ((1536, [1536]), (3, 0)),
        ((1024, [1]), (2, 1)), ((1024, [513]), (2, 0)),
        # several rows, a bucket of no whole tile: XLA's form, a tile a row
        ((16, [9, 16, 0, 0]), (4, 0)),
        ((8, [8]), None)]),                 # no longer than topk
    ("keye_debug", "xla", [
        ((1536, [700]), (3, 0)), ((16, [9, 3]), (2, 0))]),
])
def test_the_engine_says_which_form_selects_and_counts_its_tiles(
        engine, preset, form, groups):
    """``serve.engine_build`` says ``index_select`` (the kernel where a
    warmed (rows, bucket) engages it) and ``serve.prefill_group`` the
    selection's query tiles a layer and those the kernel declines, from the
    lengths the scheduler holds."""
    timeline.clear()
    server = engine(model_preset=preset, max_len=2048, fresh=True,
                    prefill_buckets=(8, 16, 1024, 1536),
                    prefill_groups=(1, 4))
    built = family.span_args(timeline.export_timeline(), "serve.engine_build")
    assert built[-1]["index_select"] == form
    for (bucket, lens), _ in groups:
        server._record_prefill_group(0.0, 1.0, bucket, np.asarray(lens),
                                     sum(n > 0 for n in lens))
    spans = family.span_args(timeline.export_timeline(),
                             "serve.prefill_group")
    assert len(spans) == len(groups)
    for span, ((bucket, lens), want) in zip(spans, groups):
        assert span["bucket"] == bucket
        got = (span.get("index_select_tiles"),
               span.get("index_select_tiles_declined"))
        assert got == (want or (None, None)), (bucket, lens)


def test_llm_server_serves_the_model_through_generate(model, engine):
    """``LLMServer.generate`` on the dense plane, no option: admission,
    prefill waves, chunks, slots reused by later requests (8 requests on 4
    slots), contexts on both sides of ``topk`` -- every reply within TOL of
    the reference; the chunks count keys present and attended."""
    cfg, params, published = model
    assert tracing.enabled()
    group = metrics.serve_engine_counters()

    def series():
        return {name: group[name].snapshot().get(("llm",), 0.0)
                for name in ("decode_kv_positions_present",
                             "decode_kv_positions_attended")}

    timeline.clear()
    before = series()
    # a server of its own: every chunk on the timeline is counted, and it
    # is shut down (the scheduler's thread joined) before they are
    server = engine(params=params, model_preset="keye_debug_f32",
                    fresh=True)
    assert set(server.cache) == {"k", "v", "ik"}
    family.serves_through_generate(
        server, ((5, 9), (16, 12), (23, 7), (1, 14), (30, 6), (2, 4), (9, 5),
                 (17, 11)),
        lambda prompt, tokens: _gap(params, prompt, tokens, published,
                                    pad_to=64), TOL)
    family.settle(server)
    stats = server.kv_stats()
    server.shutdown()
    events = timeline.export_timeline()
    assert family.span_args(events, "serve.engine_build")[-1][
        "index_select"] == "xla"
    groups = family.span_args(events, "serve.prefill_group")
    assert groups and all(        # a bucket of no whole tile: one a row
        g["index_select_tiles"] == g["rows_padded"]
        and g["index_select_tiles_declined"] == 0 for g in groups)
    chunks = family.span_args(events, "serve.chunk")
    assert chunks
    for c in chunks:
        assert "index_keys_scored" not in c     # one a position present
        assert c["kv_positions_attended"] <= min(
            c["kv_positions_present"], TOPK * c["active"])
        assert "state_rows_updated" not in c
    assert any(c["kv_positions_attended"] < c["kv_positions_present"]
               for c in chunks)
    moved = {name: series()[name] - before[name] for name in before}
    assert moved["decode_kv_positions_present"] == sum(
        c["kv_positions_present"] for c in chunks)
    assert moved["decode_kv_positions_attended"] == sum(
        c["kv_positions_attended"] for c in chunks)
    assert "state_pool" not in stats or "conv_bytes" not in stats.get(
        "state_pool", {})

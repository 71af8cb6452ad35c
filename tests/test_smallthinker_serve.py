"""A decoder whose layers mix full NoPE attention and sliding-window RoPE
attention (SmallThinker's shape, toy widths, a window of 8) through the
dense serving plane, held to ``benchmarks/references/
smallthinker_decoder.py``: float32, the window an explicit mask over
every key, every expert on every token, no cache and no ring.

- prefill then decode through ``build_prefill`` / ``build_decode_k``
  against the reference's full forward pass (logits, not tokens), for
  prompts below, at and past the window, the ring wrapping twice and more
  during decode, through the masked einsum and through the banded flash
  forward;
- a reused slot inherits nothing;
- four broken variants each FAIL the comparison;
- the banded flash forward against the masked einsum, and
  ``decode_attention`` over rows against ``llama._cache_attend``, at 4 kv
  heads;
- the router on the layer's input and the ReLU gate are not the router
  after attention and SiLU on the same weights;
- the planes built on rows by position refuse the config; spans, counters
  and pool sizes exist for a windowed model and only for one;
- the configurations the benchmark had before build the cache trees and
  lower the programs they did at the parent commit.
"""

import functools
import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.references import smallthinker_decoder as reference
from ray_tpu.models import llama, llama_serve, moe
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import metrics, timeline, tracing
from ray_tpu.ops import decode_attention as decode_attention_module

VOCAB, MAX_LEN, WINDOW = 256, 64, 8
# Float32 throughout, as the reference: the two differ by the ORDER of
# float32 sums alone, a gap is a near-tie of ~1e-5 deviations.  A broken
# variant emits arbitrary tokens: gaps of whole deviations.
TOL = 1e-3


def _cfg(**kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=64, n_layers=8, n_heads=8,
        n_kv_heads=4, head_dim=16, intermediate_size=32, moe_experts=8,
        moe_top_k=3, moe_norm_topk=True, moe_router_input="layer",
        moe_activation="relu", window_size=WINDOW,
        layer_pattern=("attention", "window", "window", "window"),
        nope_kinds=("attention",), tie_embeddings=False,
        max_seq_len=MAX_LEN, dtype=jnp.float32)
    base.update(kw)
    return LlamaConfig.debug(**base)


def _published(cfg):
    """The toy configuration in the published key names (what the
    reference reads)."""
    periods = cfg.n_layers // len(cfg.period)
    windowed = [int(kind == "window") for kind in cfg.period] * periods
    return {"num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "moe_num_active_primary_experts": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_norm_topk,
            "moe_primary_router_apply_softmax": True,
            "rope_layout": windowed, "sliding_window_layout": windowed,
            "sliding_window_size": cfg.window_size, "rope_scaling": None,
            "tie_word_embeddings": False}


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, family.init_params(jax.random.key(7), cfg)


def _gap(cfg, params, prompt, emitted):
    """The largest RAW gap: in float32 the engine breaks no near-tie the
    other way, so nothing is to be taken out."""
    return float(reference.teacher_forced_report(
        params, prompt, emitted, _published(cfg))["gap"].max())


# ----------------------------------------------- engine against reference
@pytest.mark.parametrize("flash", [False, True], ids=["einsum", "flash"])
@pytest.mark.parametrize("prompt_len,new_tokens", [
    (3, 4),         # never fills the window
    (5, 24),        # below the window, decode wraps the ring twice
    (8, 12),        # exactly the window
    (9, 12),        # one past: the prefill's ring has wrapped
    (21, 21),       # past twice the window, then two more laps
    (40, 17),       # five laps in the prefill
])
def test_prefill_then_decode_against_the_full_forward_pass(
        model, monkeypatch, flash, prompt_len, new_tokens):
    cfg, params = model
    if flash:
        monkeypatch.setattr(llama, "FLASH_PREFILL_FROM", 0)
    prompt = np.random.default_rng(prompt_len).integers(
        0, VOCAB, prompt_len).astype(np.int32)
    # one case keeps its own bucket: 8 rows in a bucket of 8, which is the
    # window too -- the row that fills its bucket exactly and the bucket
    # that equals the window; every other length is data in the file's one
    # bucket (the cache's whole length)
    bucket = 8 if prompt_len == 8 else None
    emitted, _ = family.serve_one(cfg, params, prompt, new_tokens,
                                  bucket=bucket)
    assert _gap(cfg, params, prompt, emitted) <= TOL


def test_a_long_prompt_crosses_tiles_of_the_banded_flash_forward(
        monkeypatch):
    """2,500 positions, tiles of 512, a window of 700: tiles skipped
    behind the band, tiles both of its edges cross, and a ring that the
    prompt laps three times."""
    cfg = _cfg(window_size=700, max_seq_len=4096, n_layers=4)
    params = family.init_params(jax.random.key(3), cfg)
    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "DEFAULT_BLOCK", 512)
    monkeypatch.setattr(llama, "FLASH_PREFILL_FROM", 2048)
    prompt = np.random.default_rng(1).integers(0, VOCAB, 2500).astype(
        np.int32)
    cache = llama_serve.init_cache(cfg, 2, 4096)
    toks = np.zeros((1, 2560), np.int32)
    toks[0, :2500] = prompt
    cache, first, _ = llama_serve.build_prefill(cfg)(
        params, cache, jnp.asarray(toks), jnp.asarray([2500], jnp.int32),
        jnp.asarray([1], jnp.int32))
    decode_k = llama_serve.build_decode_k(cfg)
    tok = jnp.zeros(2, jnp.int32).at[1].set(first[0])
    lens = jnp.zeros(2, jnp.int32).at[1].set(2500)
    active = jnp.asarray([False, True])
    zeros, off = jnp.zeros(2, jnp.int32), jnp.zeros(2, bool)
    _, out, _, _, _ = decode_k(params, cache, tok, lens, zeros, zeros, off,
                               active, k=4, s_active=4096)
    emitted = [int(first[0])] + [int(t) for t in np.asarray(out)[:, 1]]
    assert _gap(cfg, params, prompt, emitted) <= TOL


def test_a_reused_slot_inherits_nothing(model):
    """A long request, then a short one in the same slot: the short one's
    tokens are those it gets in a fresh cache, though the rings and the
    full pool still hold the first one's rows past its length."""
    cfg, params = model
    family.reused_slot_inherits_nothing(
        functools.partial(family.serve_one, cfg, params),
        functools.partial(_gap, cfg, params), TOL)


@pytest.mark.parametrize("variant", ["window_off_by_one", "unroped_window",
                                     "roped_global", "router_after"])
def test_a_broken_variant_fails_the_reference(model, variant):
    """What the comparison is there to catch, each with the same weights:
    a window one key too wide, a window layer without RoPE, a global
    layer with it, the router reading the stream after attention."""
    cfg, params = model
    broken = {
        "window_off_by_one": _cfg(window_size=WINDOW + 1),
        "unroped_window": _cfg(nope_kinds=("attention", "window")),
        "roped_global": _cfg(nope_kinds=()),
        "router_after": _cfg(moe_router_input="ffn"),
    }[variant]
    prompt = np.random.default_rng(11).integers(0, VOCAB, 21).astype(
        np.int32)
    emitted, _ = family.serve_one(broken, params, prompt, 24)
    family.reads_as(_gap(cfg, params, prompt, emitted), variant, TOL)


# ------------------------------------------------------------ the kernels
@pytest.mark.parametrize("window", [None, 40, 128, 700])
def test_banded_flash_forward_is_the_masked_einsum_at_4_kv_heads(window):
    from ray_tpu.ops.flash_attention import (_fwd,
                                             flash_prefill_attention)

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 256, h, 16)), jnp.float32)
               for h in (8, 4, 4))
    positions = jnp.arange(256)[None]
    want = llama.dot_attention(q, k, v, positions, 0.25, window)
    got = flash_prefill_attention(q, k, v, scale=0.25, window=window)
    assert float(jnp.abs(got - want).max()) < 1e-5
    # tiles of 32: skipped, crossed by one edge, by both, untouched
    t = lambda x: jnp.transpose(x, (0, 2, 1, 3))
    tiled, _ = _fwd(t(q) * 0.25, t(k), t(v), causal=True, block_q=32,
                    block_k=32, interpret=True, window=window)
    assert float(jnp.abs(t(tiled) - want).max()) < 1e-5


@pytest.mark.parametrize("ring", [False, True])
def test_decode_attention_over_rows_at_4_kv_heads(ring):
    """The cache handed over as ``(L, B, S * 4, D)`` rows: each row
    attends its first ``min(length + 1, s_active, S)`` positions, as
    ``_cache_attend`` over the same rows with the same keys visible."""
    rng = np.random.default_rng(2)
    L, B, S, hkv, hq, d = 3, 5, 16, 4, 8, 16
    ck, cv = (jnp.asarray(rng.normal(size=(L, B, S, hkv, d)), jnp.float32)
              for _ in range(2))
    q = jnp.asarray(rng.normal(size=(B, hq, d)), jnp.float32)
    lens = jnp.asarray([0, 3, 15, 40, 9], jnp.int32)  # 40: a ring's only
    active = jnp.asarray([True, True, True, ring, False])
    got = decode_attention_module.decode_attention(
        q, ck.reshape(L, B, S * hkv, d), cv.reshape(L, B, S * hkv, d),
        jnp.int32(1), lens, active, s_active=64, scale=0.25, hkv=hkv)
    seen = jnp.minimum(lens, S - 1)     # every key of a full ring
    want = llama._cache_attend(q[:, None], ck[1], cv[1], seen[:, None],
                               0.25)[:, 0]
    want = jnp.where(active[:, None, None], want, 0.0)
    assert float(jnp.abs(got - want).max()) < 1e-5


# ------------------------------------------------- the router and the gate
def test_router_on_the_layers_input_and_relu_gate(model):
    cfg, params = model
    layer = {k: v[1] for k, v in params["layers"].items()
             if k not in llama.ATTENTION_LEAVES}
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(1, 6, 64)), jnp.float32)
    attn = jnp.asarray(rng.normal(size=(1, 6, 8, 16)), jnp.float32)
    layer["wo"] = params["layers"]["wo"][1]
    outs = {}
    for name, kw in (("published", {}),
                     ("router_after", {"moe_router_input": "ffn"}),
                     ("silu", {"moe_activation": "silu"})):
        outs[name], _aux, rows = llama.attn_out_ffn(
            x, attn, layer, _cfg(**kw))
        assert int(rows.sum()) == 6 * 3
    assert float(jnp.abs(outs["published"] - outs["router_after"]).max()) \
        > 1e-3
    assert float(jnp.abs(outs["published"] - outs["silu"]).max()) > 1e-3
    # the published one by hand: route on x, compute on the normed stream
    x1 = x + attn.reshape(1, 6, 128) @ layer["wo"]
    h = llama.rms_norm(x1, layer["mlp_norm"], cfg.norm_eps)
    probs = jax.nn.softmax(x[0] @ layer["router"], -1)
    top, idx = jax.lax.top_k(probs, 3)
    gates = top / top.sum(-1, keepdims=True)
    y = jnp.zeros((6, 64))
    for t in range(6):
        for g, e in zip(gates[t], idx[t]):
            act = jax.nn.relu(h[0, t] @ layer["w_gate"][e]) \
                * (h[0, t] @ layer["w_up"][e])
            y = y.at[t].add(g * (act @ layer["w_down"][e]))
    assert float(jnp.abs(outs["published"][0] - (x1[0] + y)).max()) < 1e-4
    relu = moe.MoEConfig(64, 32, activation="relu")
    assert relu.act is jax.nn.relu
    assert moe.MoEConfig(64, 32).act is jax.nn.silu


def test_the_report_gives_each_layers_choice_of_experts(model):
    cfg, params = model
    prompt = np.arange(1, 12, dtype=np.int32)
    emitted, _ = family.serve_one(cfg, params, prompt, 6)
    report = reference.teacher_forced_report(params, prompt, emitted,
                                             _published(cfg))
    L, k = cfg.n_layers, cfg.moe_top_k
    assert report["gap"].shape == (6,)
    assert report["chosen"].shape == (L, 6, k)
    # layer 0 routes on the embedding itself
    rows = np.concatenate([prompt, emitted])[len(prompt) - 1:][:6]
    r = np.asarray(params["embed_tokens"])[rows] \
        @ np.asarray(params["layers"]["router"][0])
    order = np.argsort(-r, -1)
    assert (np.sort(report["chosen"][0], -1)
            == np.sort(order[:, :k], -1)).all()


@pytest.mark.parametrize("over,stands", [(26, False), (27, True)])
def test_near_tie_swaps_are_taken_out_up_to_the_count_allowed(over, stands):
    """100 positions may hold 8 + 18 swaps: those are set to zero and the
    rest stands; one more and the request is given back as it was read."""
    assert reference.swaps_allowed(100) == 26
    gap = np.full(100, 0.01)
    gap[:over] = 0.4
    gap[-1] = reference.SWAP_GAP          # at it, not over it
    out = reference.take_out_swaps(gap)
    assert out.shape == gap.shape
    if stands:
        assert (out == gap).all()
    else:
        assert float(out.max()) == reference.SWAP_GAP
        assert (out[:over] == 0).all() and (out[over:] == gap[over:]).all()


def test_config_refusals():
    with pytest.raises(ValueError, match="window_size"):
        _cfg(window_size=0)
    with pytest.raises(ValueError, match="do not mix"):
        LlamaConfig.hybrid_debug(
            layer_pattern=("mamba", "window", "attention"), window_size=8)
    with pytest.raises(ValueError, match="nope_kinds"):
        _cfg(nope_kinds=("mamba",))
    with pytest.raises(ValueError, match="moe_router_input"):
        _cfg(moe_router_input="before")
    with pytest.raises(ValueError, match="moe_activation"):
        _cfg(moe_activation="gelu")


def test_training_refuses_the_config(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="served only"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="window"):
        llama.forward_with_cache(
            params, jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), {}, cfg)


# -------------------------------------------------------------- the engine
_presets = family.presets({"windowed_debug_f32": _cfg})
engine = family.engines("windowed_debug_f32", max_len=MAX_LEN)


def test_llm_server_serves_the_windowed_model_through_generate(model,
                                                               engine):
    """``LLMServer.generate`` on the dense plane, no option: admission,
    prefill waves of several rows, chunks, slots reused by later requests
    (8 requests on 4 slots) -- every reply within TOL of the reference."""
    cfg, params = model
    family.serves_through_generate(
        engine(params=params),
        ((5, 19), (16, 12), (23, 17), (1, 24), (30, 6), (8, 10), (9, 25),
         (17, 11)), functools.partial(_gap, cfg, params), TOL)


@pytest.mark.parametrize("plane,args", family.PLANES)
def test_planes_built_on_rows_by_position_refuse_the_config(plane, args):
    family.refuses_plane(
        "windowed_debug_f32", plane, args, "window layers",
        words=(f"ring of the last {WINDOW} positions",))


def test_spans_counters_and_pools_for_a_windowed_model_and_only_for_one(
        engine):
    """``serve.chunk`` carries the keys its rows hold by pool,
    ``serve.prefill_group`` the band's share of the bucket's square,
    ``kv_stats()`` and ``ray_tpu_kv_pool_bytes`` both pools; an engine of
    a plain decoder emits none of it."""
    assert tracing.enabled()
    pools = metrics.kv_cache_counters()
    timeline.clear()
    # servers of its own, this one and the plain one below: every span on
    # the timeline is counted, and the last chunk's is written by the time
    # the scheduler's thread has been joined (``shutdown``)
    server = engine(fresh=True)
    cfg = server.cfg
    family.generate(server, [{"prompt": list(range(1, 1 + n)),
                              "max_new_tokens": 9} for n in (5, 12, 20)])
    stats = server.kv_stats()
    server.shutdown()
    # K and V of 2 full layers x 64 positions and 6 rings x 8, 4 x 16 wide
    full, ring = 2 * 2 * 64 * 4 * 16 * 4, 2 * 6 * 8 * 4 * 16 * 4
    assert llama_serve.cache_pools(cfg, 4, 64) == {
        "kv_full": (4 * full, "float32"), "kv_window": (4 * ring, "float32")}
    assert stats["kv_pools"]["kv_full"]["bytes_per_slot"] == full
    assert stats["kv_pools"]["kv_window"] == {
        "bytes": 4 * ring, "dtype": "float32", "bytes_per_slot": ring,
        "ring_positions": 8}
    snapshot = pools["pool_bytes"].snapshot()
    assert snapshot[("llm.kv_full", "float32")] == 4 * full
    assert snapshot[("llm.kv_window", "float32")] == 4 * ring
    spans = timeline.export_timeline()
    groups = family.span_args(spans, "serve.prefill_group")
    chunks = family.span_args(spans, "serve.chunk")
    assert groups and chunks
    for g in groups:
        b = g["bucket"]
        assert g["window_band_share"] == pytest.approx(
            8 * (2 * b - 7) / (b * b), abs=1e-4)
        assert g["expert_rows"] == 8 * 3 * g["prompt_tokens"]
    for c in chunks:
        assert c["kv_full_positions_attended"] == c["kv_positions_attended"]
        assert c["kv_window_positions_attended"] <= 8 * c["active"]
        assert (c["kv_full_layers"], c["kv_window_layers"]) == (2, 6)
        assert c["kv_window_bucket"] == 8 <= c["kv_full_bucket"]
    assert any(c["kv_window_positions_attended"]
               < c["kv_full_positions_attended"] for c in chunks)

    timeline.clear()
    plain = engine(model_preset="debug", fresh=True)
    family.generate(plain, [{"prompt": [1, 2, 3], "max_new_tokens": 5}])
    assert "kv_pools" not in plain.kv_stats()
    plain.shutdown()
    spans = timeline.export_timeline()
    for name in ("serve.chunk", "serve.prefill_group"):
        for args in family.span_args(spans, name):
            assert not [k for k in args if "window" in k
                        or k.startswith("kv_full")]


# ------------------------------- what the benchmark had is what it still has
# The cache trees, the parameter trees and the lowered text of ``prefill``
# and ``decode_k`` at toy widths (kernels interpreted) of a plain, a
# grouped-query untied, two expert and two hybrid configurations, as the
# commit before the window kind (888cb52, PR 31) lowered them, by sha256.
# A later PR that means to change a program records its new text here:
# PR 33 did for the six ``prefill`` programs (a group's rows written where
# they lie, ``llama_serve._insert_rows``; the ``decode_k`` texts are
# PR 31's still) and for the windowed one below; PR 61 did for the two
# hybrids' four programs (Mamba-2's B and C carry a group axis through the
# chunked scan, the state update and the gated norm: one group there, so
# unit dimensions; compiled for a v5e at granite's widths the decode step
# differs from its parent's in 27 bitcasts and the prefill in nothing:
# PERF.md section 6, PR 61) and for ``olmoe_like``'s ``decode_k``, whose 4
# slots x top-3 are 12 sorted rows and not whole sublane tiles:
# ``moe._sorted_ffn`` now gathers 16 (every benchmark cell's rows are whole
# tiles and their programs the text they were); PR 63 did for the two expert
# configurations' four programs (``moe._sorted_ffn`` has one un-sort-and-sum:
# a token's k-th result is gathered straight into the float32 sum, in the
# stream's type; no un-sorted float32 (T, K, D) copy of every row).
_BEFORE = {
    "dense": (LlamaConfig.debug,
              {}, "9cea23cd675cc71a", "451d4209906be1e0"),
    "dense_untied_gqa": (LlamaConfig.debug,
                         dict(tie_embeddings=False, n_kv_heads=1),
                         "5b383136284f778a", "836058ef106088be"),
    "moe": (LlamaConfig.moe_debug, {},
            "fd31f33e37df1817", "d6e027ba0b02138a"),
    "olmoe_like": (LlamaConfig.moe_debug,
                   dict(moe_norm_topk=False, qk_norm=True, moe_top_k=3),
                   "03c185f81e225cd6", "d155c85388c16566"),
    "hybrid": (LlamaConfig.hybrid_debug, {},
               "1cb35f28ca05d404", "e877b22e6a56c290"),
    "hybrid_f32_stream": (LlamaConfig.hybrid_debug,
                          dict(stream_dtype="float32"),
                          "177292a141842087", "ee8b3b308e03eecd"),
}


def _abstract(cfg):
    """The parameter and cache trees of a config as shapes (4 slots x 64),
    for ``.lower``."""
    shapes = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    params = shapes(jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = shapes(jax.eval_shape(
        lambda: llama_serve.init_cache(cfg, 4, 64)))
    return params, cache


def _lowered_prefill(cfg, params, cache, rows):
    group = jax.ShapeDtypeStruct((rows,), jnp.int32)
    return llama_serve.build_prefill(cfg).lower(
        params, cache, jax.ShapeDtypeStruct((rows, 16), jnp.int32), group,
        group).as_text()


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(_BEFORE))
def test_the_configurations_before_build_and_lower_what_they_did(name):
    preset, kw, prefill_sha, decode_sha = _BEFORE[name]
    cfg = preset(**kw)
    params, cache = _abstract(cfg)
    kv = (cfg.layers_of("attention"), 4, 64, cfg.n_kv_heads, cfg.head_dim)
    assert cache["k"].shape == cache["v"].shape == kv
    assert set(cache) == ({"k", "v", "ssm", "conv"}
                          if cfg.layers_of("mamba") else {"k", "v"})
    assert params["layers"]["wq"].shape[0] == cfg.layers_of("attention")
    ints = jax.ShapeDtypeStruct((4,), jnp.int32)
    bools = jax.ShapeDtypeStruct((4,), jnp.bool_)
    prefill = _lowered_prefill(cfg, params, cache, rows=2)
    decode = llama_serve.build_decode_k(cfg).lower(
        params, cache, ints, ints, ints, ints, bools, bools, k=4,
        s_active=32).as_text()
    assert (_sha(prefill), _sha(decode)) == (prefill_sha, decode_sha)


@pytest.mark.parametrize("rows,prefill_sha", [(1, "0135664d0f36f3b4"),
                                              (2, "b9e053996451c05f")])
def test_the_windowed_prefills_lowered_text_is_recorded(rows, prefill_sha):
    """The insert is one function with one shape rule for both layouts of
    a pool since PR 33: a member is cut out of the group as a slice.  A
    model with window layers, whose pools are stored as rows, lowered a
    member indexed out of ``(layers, G, 1, rows, D)`` at the commit before
    (d9c05df, PR 32: 472bf6d68c59c0a3 / 98bd8d52841b61c8); the text
    recorded here differs from that in the insert's reshape, slice and
    select (and the numbering of what follows them), and at cell 7's real
    widths both compile for a v5e to the same optimised HLO, instruction
    for instruction (AOT, PR 33: PERF.md section 6).  Since PR 63 the text
    is the expert layer's one un-sort and sum (``_BEFORE``'s comment)."""
    cfg = _cfg()
    assert _sha(_lowered_prefill(cfg, *_abstract(cfg), rows)) == prefill_sha

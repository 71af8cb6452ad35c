"""LFM2-8B-A1B's shape at toy widths through the dense serving plane, held
to ``benchmarks/references/lfm2_moe_decoder.py`` (float32, no cache, no
conv state, a loop over experts):

- multi-row prefill with padding, then decode through K/V and the conv
  states, against the reference's full forward pass at every position --
  for the benchmark's cut (the first 14 ``layer_types``) AND for the
  published 24-entry list with ``num_dense_layers`` 2, verbatim;
- a reused slot inherits nothing, a slot that sits out a chunk keeps its
  state;
- the router against a by-hand case where the bias changes the choice and
  not the gate;
- the broken variants of ``benchmarks/tools/lfm2_check.py`` each FAIL;
- how ``parts`` cuts a list of kinds, the parameter and cache trees,
  config refusals, the planes that refuse the model, training refused;
- ``LLMServer.generate`` end to end, spans / counters / the conv pool
  present for this model;
- the lowered text of the configurations before (cells 7 and 8's shapes;
  ``tests/test_smallthinker_serve.py`` holds cells 3-6's) is what the
  parent commit lowered.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.references import lfm2_moe_decoder as reference
from benchmarks.tools import lfm2_check
from ray_tpu.models import llama, llama_serve, moe, shortconv
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import metrics, timeline, tracing

VOCAB, SLOTS, MAX_LEN = 256, 4, 64
TOL = 1e-3          # float32 both sides: the order of sums alone
PUBLISHED = ("conv", "conv", "full_attention", "conv", "conv", "conv",
             "full_attention", "conv", "conv", "conv", "full_attention",
             "conv", "conv", "conv", "full_attention", "conv", "conv",
             "conv", "full_attention", "conv", "conv", "full_attention",
             "conv", "conv")
PATTERNS = {"cut": PUBLISHED[:14], "published": PUBLISHED}
# the cut at heads of 64 (MHA 2/2 on a stream of 128: the reference's head
# is hidden_size / heads), whose pool keeps two heads a 128-lane row
HEAD_64 = dict(hidden_size=128, n_heads=2, n_kv_heads=2, head_dim=64)


def _cfg(kinds=PUBLISHED[:14], **kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=64, n_layers=len(kinds), n_heads=4,
        n_kv_heads=2, head_dim=16, intermediate_size=128,
        max_seq_len=MAX_LEN, rope_theta=1e6, norm_eps=1e-5,
        tie_embeddings=True, remat=False, dtype=jnp.float32,
        layer_types=kinds, first_dense_layers=2, conv_taps=3,
        qk_head_norm=True, moe_experts=8, moe_top_k=2, moe_norm_topk=True,
        moe_intermediate_size=32, moe_router_score="sigmoid",
        moe_router_bias=True)
    base.update(kw)
    return LlamaConfig(**base)


def _published(cfg, kinds):
    """The toy configuration in the published key names (what the
    reference reads)."""
    return {"num_hidden_layers": cfg.n_layers, "layer_types": list(kinds),
            "num_dense_layers": cfg.first_dense_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "hidden_size": cfg.hidden_size, "rope_theta": cfg.rope_theta,
            "norm_eps": cfg.norm_eps, "conv_L_cache": cfg.conv_taps,
            "conv_bias": False, "use_expert_bias": True,
            "num_experts": cfg.moe_experts,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_norm_topk,
            "routed_scaling_factor": cfg.moe_routed_scale,
            "tie_word_embeddings": True, "vocab_size": cfg.vocab_size}


def _init(cfg, seed=7):
    """The program's own weights with the norms moved off 1, so that
    where a norm sits and which weight it takes is seen, and the selection
    bias ten times as wide (0.2: at 8 experts the scores lie further
    apart, and a bias that reached the gates has to show)."""
    def init(key, moving):
        keys = iter(jax.random.split(moving, 64))

        def moved(path, x):
            name = path[-1].key
            if name.endswith("norm"):
                return x * (1 + 0.2 * jax.random.normal(next(keys), x.shape))
            return 10 * x if name == "router_bias" else x

        return jax.tree_util.tree_map_with_path(
            moved, llama.init_params(key, cfg))

    # one program: op by op the initialiser is a hundred small compiles
    return jax.jit(init)(jax.random.key(seed), jax.random.key(seed + 1))


@pytest.fixture(scope="module", params=sorted(PATTERNS) + ["cut_head_64"])
def model(request):
    kinds = PATTERNS.get(request.param, PATTERNS["cut"])
    cfg = _cfg(kinds, **(HEAD_64 if request.param == "cut_head_64" else {}))
    return cfg, _init(cfg), _published(cfg, kinds)


@pytest.fixture(scope="module")
def cut():
    cfg = _cfg()
    return cfg, _init(cfg), _published(cfg, PUBLISHED[:14])


def _gap(params, prompt, emitted, published):
    return float(reference.teacher_forced_report(
        params, prompt, emitted, published)["gap"].max())


# ----------------------------------------------- engine against reference
def test_prefill_then_decode_through_kv_and_conv_state(model):
    """Three prompts of unlike lengths in ONE padded group (a padding row
    behind them), then decoded together, one of them sitting out a chunk
    in the middle: every emitted position of each within TOL."""
    cfg, params, published = model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
               for n in (1, 9, 30)]
    slots = (2, 0, 3)
    cache = llama_serve.init_cache(cfg, SLOTS, MAX_LEN)
    cache, first, load = family.prefill(cfg, params, cache, prompts, slots)
    expert_layers = cfg.n_layers - cfg.first_dense_layers
    assert load[0].shape == (expert_layers, cfg.moe_experts)
    # the real positions alone, top-k experts each, in every expert layer
    assert (np.asarray(load[0]).sum(1) == 40 * cfg.moe_top_k).all()
    tok, lens = family.seat(first, (1, 9, 30), slots)
    emitted = {s: [int(t)] for s, t in zip(slots, first)}
    for who in (slots, slots, (2, 3), slots, slots):
        cache, out, tok, lens, load = family.decode(cfg, params, cache, tok,
                                                    lens, who)
        assert int(np.asarray(load[0]).sum()) \
            == 4 * len(who) * cfg.moe_top_k * expert_layers
        for s in who:
            emitted[s] += [int(t) for t in out[:, s]]
    assert [len(emitted[s]) for s in slots] == [21, 17, 21]
    for prompt, s in zip(prompts, slots):
        assert _gap(params, prompt, emitted[s], published) <= TOL


def test_prefill_logits_are_the_references(model):
    """Numbers, not the leading token: each row's last real position."""
    cfg, params, published = model
    rng = np.random.default_rng(4)
    toks = rng.integers(0, VOCAB, (3, 16)).astype(np.int32)
    lengths = np.asarray([16, 5, 11], np.int32)
    got = llama.prefill_with_states(params, jnp.asarray(toks),
                                    jnp.asarray(lengths), cfg)
    want = reference.logits(params, toks, published)
    for g, n in enumerate(lengths):
        assert float(jnp.abs(got[0][g] - want[g, n - 1]).max()) <= TOL
    conv_layers = cfg.layers_of("conv")
    (state,) = got[4]
    assert state.shape == (conv_layers, cfg.conv_taps - 1, 3,
                           cfg.hidden_size)
    assert got[1].shape[0] == cfg.layers_of("attention")


def test_a_reused_slot_and_a_slot_that_sits_out(cut):
    """``lfm2_check.serve_one``: the request goes into a slot another
    request was prefilled and decoded in, and sits out a chunk the other
    slot decodes alone."""
    cfg, params, published = cut
    rng = np.random.default_rng(5)
    before, prompt = (rng.integers(0, VOCAB, n).astype(np.int32)
                      for n in (19, 7))
    emitted = lfm2_check.serve_one(cfg, params, before, prompt, 20, 32,
                                   MAX_LEN, k=4, slots=3)
    assert _gap(params, prompt, emitted, published) <= TOL


def test_a_row_of_two_heads_of_64_round_trips_bit_for_bit():
    """``init_cache`` -> a prefill group's insert -> the decode step's
    write: a pool of heads of 64 is ``(La, B, S * Hkv / 2, 128)``, and what
    the one insert and the one write leave in it is, read back by position
    and head, what they leave in ``(La, B, S, Hkv, 64)`` -- the same bytes
    in the same order."""
    cfg = _cfg(n_heads=8, n_kv_heads=4, head_dim=64)
    assert (cfg.kv_row_heads, cfg.kv_row_dim) == (2, 128)
    rows = llama_serve.init_cache(cfg, SLOTS, MAX_LEN)
    La = cfg.layers_of("attention")
    assert rows["k"].shape == rows["v"].shape == (La, SLOTS, MAX_LEN * 2, 128)
    assert llama_serve.cache_pools(cfg, SLOTS, MAX_LEN)["kv"] == (
        2 * La * SLOTS * MAX_LEN * 4 * 64 * 4, "float32")
    by_position = (La, SLOTS, MAX_LEN, 4, 64)
    rng = np.random.default_rng(60)
    group = jnp.asarray(rng.normal(size=(La, 3, 16, 4, 64)), jnp.float32)
    slots = jnp.asarray([2, -1, 0], jnp.int32)       # a padding row
    fresh = jnp.asarray(rng.normal(size=(SLOTS, 4, 64)), jnp.float32)
    pos = jnp.asarray([16, 0, MAX_LEN, 5], jnp.int32)    # one out of range
    pools = {}
    for name, pool in (("rows", rows["k"]),
                       ("by_position", jnp.zeros(by_position))):
        pool = llama_serve._insert_rows(pool, group, slots)
        pool = llama_serve._write(pool, 1, jnp.arange(SLOTS), pos, fresh)
        pools[name] = np.asarray(pool).reshape(by_position)
    np.testing.assert_array_equal(pools["rows"], pools["by_position"])
    np.testing.assert_array_equal(pools["rows"][:, 2, :16], group[:, 0])
    np.testing.assert_array_equal(pools["rows"][1, 0, 16], fresh[0])
    np.testing.assert_array_equal(pools["rows"][1, 3, 5], fresh[3])
    np.testing.assert_array_equal(pools["rows"][1, 1, 0], fresh[1])
    assert not pools["rows"][0, 1].any()        # the padding row's slot
    assert not pools["rows"][:, 2, 16:].any()   # a write past the pool


def test_conv_prefill_state_is_the_last_real_inputs(cut):
    cfg, params, _ = cut
    layer = {k: v[0] for k, v in params["dense_layers"].items()}
    h = jnp.asarray(np.random.default_rng(0).normal(size=(3, 8, 64)),
                    jnp.float32)
    lengths = jnp.asarray([8, 1, 0], jnp.int32)
    out, (state,) = shortconv.prefill(h, layer, cfg, lengths)
    u, _gate = shortconv._project(h, layer, cfg)
    assert state.shape == (2, 3, 64)
    np.testing.assert_allclose(state[:, 0], u[0, 6:8], atol=1e-6)
    np.testing.assert_allclose(state[1, 1], u[1, 0], atol=1e-6)
    assert not np.asarray(state[0, 1]).any()       # before position 0
    assert not np.asarray(state[:, 2]).any()       # a padding row
    # decode from that state is the next position of a longer prefill
    longer, _ = shortconv.prefill(h, layer, cfg, None)
    stack = jnp.zeros((3, 2, 1, 64)).at[1].set(
        shortconv.prefill(h[:1, :5], layer, cfg, None)[1][0])
    step, stack = shortconv.decode(h[:1, 5:6], layer, cfg, stack,
                                   jnp.int32(1), jnp.asarray([True]))
    np.testing.assert_allclose(step[0, 0], longer[0, 5], atol=1e-5)
    np.testing.assert_allclose(stack[1, :, 0], u[0, 4:6], atol=1e-6)
    kept = shortconv.decode(h[:1, 6:7], layer, cfg, stack, jnp.int32(1),
                            jnp.asarray([False]))[1]
    assert (np.asarray(kept) == np.asarray(stack)).all()


# ------------------------------------------------------------- the router
def test_the_bias_changes_the_choice_and_not_the_gate():
    """Two tokens over four experts, top-2, by hand.  Token 0's scores
    order 0 > 1 > 2 > 3 and the bias lifts expert 3 over expert 1: chosen
    {0, 3}, gates from the SCORES of 0 and 3.  Token 1's margin is wider
    than the bias: its choice stands."""
    want = np.asarray([[0.8, 0.6, 0.5, 0.55], [0.9, 0.7, 0.2, 0.1]])
    logits = np.log(want / (1 - want))
    # token t is the t-th unit vector, the router's row t its logits
    x, router = jnp.eye(2), jnp.asarray(logits, jnp.float32)
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.1])
    scores, gates, chosen = moe._route(x, router, 2, True,
                                       score="sigmoid", bias=bias)
    np.testing.assert_allclose(scores, want, atol=1e-5)
    assert np.asarray(chosen).tolist() == [[0, 3], [0, 1]]
    np.testing.assert_allclose(
        gates, [[0.8 / (1.35 + 1e-6), 0.55 / (1.35 + 1e-6)],
                [0.9 / (1.6 + 1e-6), 0.7 / (1.6 + 1e-6)]], atol=1e-5)
    # without the bias token 0 takes expert 1
    _, plain, unbiased = moe._route(x, router, 2, True, score="sigmoid")
    assert np.asarray(unbiased).tolist() == [[0, 1], [0, 1]]
    np.testing.assert_allclose(plain[0], [0.8 / (1.4 + 1e-6),
                                          0.6 / (1.4 + 1e-6)], atol=1e-5)
    # the softmax router of every configuration before is as it was
    probs, g, idx = moe._route(x, router, 2, False)
    np.testing.assert_allclose(probs, jax.nn.softmax(logits, -1), atol=1e-5)
    np.testing.assert_allclose(g, np.sort(np.asarray(probs), -1)[:, :1:-1],
                               atol=1e-6)


def test_the_dropless_layer_is_the_references_loop_over_experts(cut):
    """One expert layer's FFN: the grouped matmuls under the biased
    sigmoid router against every expert on every token."""
    cfg, params, _ = cut
    layer = {k: v[1] for k, v in params["layers"].items()
             if k in ("router", "router_bias", "w_gate", "w_up", "w_down")}
    h = jnp.asarray(np.random.default_rng(1).normal(size=(2, 128, 64)),
                    jnp.float32)
    mcfg = moe.MoEConfig(hidden_size=64, intermediate_size=32, n_experts=8,
                         top_k=2, dtype=jnp.float32, score="sigmoid")
    got, _aux, rows = moe.moe_ffn_dropless(h, layer, mcfg)
    flat = h.reshape(256, 64)
    gates, chosen = reference._route(flat, layer["router"],
                                     layer["router_bias"], 2, True, 1.0)
    want = reference._experts(flat, gates, layer["w_gate"], layer["w_up"],
                              layer["w_down"])
    assert float(jnp.abs(got.reshape(256, 64) - want).max()) < 1e-5
    assert int(rows.sum()) == 512
    # the bias decides for a share of the tokens: the mechanism is not idle
    bare = reference._route(flat, layer["router"], None, 2, True, 1.0)[1]
    assert (np.sort(chosen, -1) != np.sort(bare, -1)).any()


# --------------------------------------------------- the broken programs
@pytest.mark.parametrize("variant", lfm2_check.VARIANTS)
def test_a_broken_variant_fails_the_reference(cut, variant):
    """The same weights under a program that is wrong in one place
    (``benchmarks/tools/lfm2_check.py`` runs the same variants at the
    published widths on the chip): over the margin, where the intact
    program reads under 0.001."""
    cfg, params, published = cut
    rng = np.random.default_rng(0)
    before, prompt = (rng.integers(0, VOCAB, n).astype(np.int32)
                      for n in (11, 21))
    vcfg, patched, weights = lfm2_check.broken(variant, cfg)
    with patched():
        emitted = lfm2_check.serve_one(vcfg, weights(params), before, prompt,
                                       24, 32, MAX_LEN, k=4, slots=3)
    family.reads_as(_gap(params, prompt, emitted, published), variant, TOL)


# ------------------------------------------- parts, trees and refusals
def test_runs_of_whole_periods():
    a, c = "attention", "conv"
    assert llama._runs((c, c)) == [(0, (c,), 2)]
    assert llama._runs((a, c, c, c) * 3) == [(0, (a, c, c, c), 3)]
    assert llama._runs((a, c, c, c) * 4 + (a, c, c) * 2) == [
        (0, (a, c, c, c), 4), (16, (a, c, c), 2)]
    assert llama._runs((a, c, c)) == [(0, (a, c, c), 1)]
    assert llama._runs((a, a, a)) == [(0, (a,), 3)]


def test_parts_and_trees_of_the_published_list():
    cfg = _cfg(PUBLISHED)
    parts = cfg.parts()
    assert [(key, l0, part.n_layers, part.layer_pattern, part.moe_experts)
            for part, key, l0 in parts] == [
        ("dense_layers", 0, 2, ("conv",), 0),
        ("layers", 2, 16, ("attention", "conv", "conv", "conv"), 8),
        ("layers_1", 18, 6, ("attention", "conv", "conv"), 8)]
    assert all(not part.layer_types and not part.first_dense_layers
               for part, _, _ in parts)
    assert (cfg.layers_of("attention"), cfg.layers_of("conv")) == (6, 18)
    assert cfg.layers_before(18, "attention") == 4
    assert cfg.layers_before(18, "conv") == 14
    assert LlamaConfig.debug().parts()[0][1:] == ("layers", 0)
    with pytest.raises(ValueError, match="a period a part"):
        cfg.period
    params = jax.eval_shape(lambda k: llama.init_params(k, cfg),
                            jax.random.key(0))
    assert set(params) == {"embed_tokens", "final_norm", "dense_layers",
                           "layers", "layers_1"}
    assert "wq" not in params["dense_layers"]
    assert "router" not in params["dense_layers"]
    assert params["dense_layers"]["conv_in"].shape == (2, 64, 192)
    assert params["dense_layers"]["w_gate"].shape == (2, 64, 128)
    assert params["layers"]["wq"].shape[0] == 4
    assert params["layers"]["q_norm"].shape == (4, 16)
    assert params["layers"]["conv_w"].shape == (12, 3, 64)
    assert params["layers"]["router_bias"].shape == (16, 8)
    assert params["layers"]["router_bias"].dtype == jnp.float32
    assert params["layers_1"]["conv_out"].shape == (4, 64, 64)
    assert params["layers_1"]["w_gate"].shape == (6, 8, 64, 32)
    axes = llama.param_logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(
        lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, params))
    cache = jax.eval_shape(lambda: llama_serve.init_cache(cfg, 4, 64))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (6, 4, 64, 2, 16), "v": (6, 4, 64, 2, 16),
        "conv": (18, 2, 4, 64)}
    pools = llama_serve.cache_pools(cfg, 4, 64)
    assert set(pools) == {"kv", "conv"}
    assert llama_serve.state_bytes_per_slot(cfg) == {
        "conv": 18 * 2 * 64 * 4}


def test_config_refusals():
    with pytest.raises(ValueError, match="names each of the n_layers"):
        _cfg(n_layers=13)
    with pytest.raises(ValueError, match="in place of layer_pattern"):
        _cfg(layer_pattern=("attention", "conv"))
    with pytest.raises(ValueError, match="unknown kinds"):
        _cfg(("conv", "conv", "linear"))
    with pytest.raises(ValueError, match="do not mix"):
        _cfg(("conv", "conv", "mamba"), ssm_heads=4)
    with pytest.raises(ValueError, match="conv_taps"):
        _cfg(conv_taps=1)
    with pytest.raises(ValueError, match="choose one"):
        _cfg(qk_norm=True)
    with pytest.raises(ValueError, match="moe_router_score"):
        _cfg(moe_router_score="tanh")
    with pytest.raises(ValueError, match="group-limited"):
        _cfg(moe_groups=4, moe_top_groups=2)
    # a pattern of whole periods needs no list: the same model
    whole = LlamaConfig.debug(n_layers=8, layer_pattern=(
        "attention", "conv", "conv", "conv"), dtype=jnp.float32)
    assert whole.layers_of("conv") == 6 and len(whole.parts()) == 1


def test_training_and_the_one_stack_cache_refuse_the_config(cut):
    cfg, params, _ = cut
    with pytest.raises(NotImplementedError, match="short-convolution"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="short-convolution"):
        llama.forward_with_cache(params, jnp.zeros((1, 1), jnp.int32),
                                 jnp.zeros((1, 1), jnp.int32), {}, cfg)
    # the conv layers alone refuse it: each other mechanism alone is
    # trained since PR 57 (tests/test_trinity_train.py)
    for kw in (dict(qk_head_norm=True),
               dict(moe_experts=4, moe_router_score="sigmoid"),
               dict(moe_experts=4, moe_router_bias=True),
               dict(layer_types=("attention", "attention"))):
        assert LlamaConfig.debug(**kw).plain_decoder


# ------------------------------------------------- through the scheduler
_presets = family.presets({
    "lfm2_debug_f32": _cfg,
    "lfm2_debug": lambda **kw: _cfg(**{"dtype": jnp.bfloat16, **kw})})
engine = family.engines("lfm2_debug", max_len=128)


def test_llm_server_serves_the_model_through_generate(cut, engine):
    """``LLMServer.generate`` on the dense plane, no option: admission,
    prefill waves, chunks, slots reused by later requests (8 requests on
    4 slots) -- every reply within TOL of the reference."""
    cfg, params, published = cut
    server = engine(params=params, model_preset="lfm2_debug_f32")
    bias = server.params["layers"]["router_bias"]
    assert bias.dtype == jnp.float32
    family.serves_through_generate(
        server, ((5, 9), (16, 12), (23, 7), (1, 14), (30, 6), (8, 10),
                 (9, 5), (17, 11)),
        lambda prompt, tokens: reference.teacher_forced_gap(
            params, prompt, tokens, published, pad_to=64).max(), TOL)


def test_a_bfloat16_engine_keeps_the_bias_float32(engine):
    server = engine()
    assert server.params["layers"]["router_bias"].dtype == jnp.float32
    assert server.params["layers"]["router"].dtype == jnp.bfloat16
    assert server.cache["conv"].dtype == jnp.bfloat16


@pytest.mark.parametrize("plane,args", family.PLANES)
def test_planes_that_cannot_hold_a_state_refuse_the_config(plane, args):
    """Blocks, shared prefixes, a rejected draft's rewind, a K/V hand-off
    and K/V quantization all rest on a cache of rows by position; a conv
    state is not one, as a recurrent state is not."""
    family.refuses_plane("lfm2_debug", plane, args, "short-convolution",
                         words=("not rows by position",),
                         absent=("state-space",))


def test_spans_counters_and_the_conv_pool(engine):
    """``serve.chunk`` carries ``state_rows_updated`` / ``state_bytes``
    and the expert load, the series
    ``ray_tpu_serve_state_bytes_total{kind=conv}`` counts the same bytes
    and no ``ssm`` ones, ``serve.prefill_group`` has no ``scan_chunks``
    (nothing is scanned), and ``kv_stats()`` / the pool gauges say what
    the cache holds."""
    assert tracing.enabled()
    group = metrics.serve_engine_counters()
    pools = metrics.kv_cache_counters()

    def series():
        return {kind: group["state_bytes"].snapshot().get(("llm", kind), 0.0)
                for kind in ("ssm", "conv")}

    timeline.clear()
    before = series()
    # a server of its own, though its arguments are the bfloat16 test's:
    # every span on the timeline is counted, and the last chunk's is written
    # by the time the scheduler's thread has been joined (``shutdown``)
    server = engine(fresh=True)
    cfg = server.cfg
    requests = [{"prompt": list(range(1, 1 + n)), "max_new_tokens": 6}
                for n in (5, 9, 20)]
    family.generate(server, requests)
    stats = server.kv_stats()
    server.shutdown()
    per_slot = llama_serve.state_bytes_per_slot(cfg)
    assert per_slot == {"conv": 11 * 2 * 64 * 2}    # 11 layers, bfloat16
    spans = timeline.export_timeline()
    groups = family.span_args(spans, "serve.prefill_group")
    chunks = family.span_args(spans, "serve.chunk")
    assert groups and chunks
    for g in groups:
        assert "scan_chunks" not in g
        assert g["expert_rows"] == g["prompt_tokens"] * 2 * 12
    for c in chunks:
        assert c["state_rows_updated"] == c["active"] * c["k"]
        assert c["state_bytes"] == 2 * c["state_rows_updated"] \
            * per_slot["conv"]
        assert c["expert_rows"] == c["active"] * c["k"] * 2 * 12
        assert 0 < c["experts_touched"] <= c["k"] * 12 * 8
    moved = {kind: series()[kind] - before[kind] for kind in before}
    rows = sum(c["state_rows_updated"] for c in chunks)
    assert moved == {"ssm": 0.0, "conv": 2.0 * rows * per_slot["conv"]}
    assert stats["state_pool"]["conv_bytes"] == 4 * per_slot["conv"]
    assert stats["state_pool"]["conv_dtype"] == "bfloat16"
    assert stats["state_pool"]["kv_bytes"] == 2 * 3 * 4 * 128 * 2 * 16 * 2
    assert stats["state_pool"]["bytes_per_slot"] == per_slot
    assert pools["state_pool_bytes"].snapshot()[
        ("llm", "conv", "bfloat16")] == 4 * per_slot["conv"]
    assert pools["pool_bytes"].snapshot()[("llm", "bfloat16")] \
        == stats["state_pool"]["kv_bytes"]


# ------------------------------- what the benchmark had is what it still has
# ``tests/test_smallthinker_serve.py`` holds the lowered text of a plain, a
# grouped-query, two expert and two hybrid configurations (cells 3-6's
# shapes) and of the windowed prefill by sha256, and passes untouched.
# Here: ``prefill`` and ``decode_k`` of the windowed toy (cell 7's shape)
# and of the latent / shared / held toy with a leading dense layer (cell
# 8's), whose ``parts``, walk, router and state pool this PR reaches into,
# as the PARENT commit (124731d, PR 39) lowered them -- but for both
# ``decode_k`` texts since PR 61: 4 slots x top-3 are 12 sorted rows, not
# whole sublane tiles, and ``moe._sorted_ffn`` now gathers 16 (the cells'
# rows are whole tiles and their programs lower to the text they did); and
# for all four texts since PR 63: ``moe._sorted_ffn`` gathers a token's k-th
# result straight into the float32 sum, held or whole, in the stream's type
# (the un-sorted copy of every row, which a share reshaped to (T, K x D) and
# a whole configuration to a float32 (T, K, D), is gone).
_YARN = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
         "mscale": 0.707, "mscale_all_dim": 0.707,
         "original_max_position_embeddings": 16}
_BEFORE = {
    "windowed": (dict(
        vocab_size=256, hidden_size=64, n_layers=8, n_heads=8, n_kv_heads=4,
        head_dim=16, intermediate_size=32, moe_experts=8, moe_top_k=3,
        moe_norm_topk=True, moe_router_input="layer", moe_activation="relu",
        window_size=8, layer_pattern=("attention", "window", "window",
                                      "window"),
        nope_kinds=("attention",), tie_embeddings=False, max_seq_len=64),
        "05a755b177536528", "38e267d825665734"),
    "latent_share": (dict(
        vocab_size=256, hidden_size=64, n_layers=3, n_heads=4, n_kv_heads=4,
        head_dim=24, intermediate_size=128, max_seq_len=64,
        rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=False,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_scaling=_YARN,
        first_dense_layers=1, moe_experts=16, moe_top_k=3,
        moe_norm_topk=False, moe_intermediate_size=32, moe_shared_size=64,
        moe_groups=4, moe_top_groups=2, moe_routed_scale=16.0,
        moe_held=(0, 8)),
        "d1b60ebf6084b9a7", "c3e0487b90f1cd7d"),
}


def _sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(_BEFORE))
def test_the_configurations_before_lower_what_the_parent_lowered(name):
    kw, prefill_sha, decode_sha = _BEFORE[name]
    cfg = LlamaConfig.debug(**kw)
    shapes = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    params = shapes(jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = shapes(jax.eval_shape(
        lambda: llama_serve.init_cache(cfg, 4, 64)))
    group = jax.ShapeDtypeStruct((2,), jnp.int32)
    ints = jax.ShapeDtypeStruct((4,), jnp.int32)
    bools = jax.ShapeDtypeStruct((4,), jnp.bool_)
    prefill = llama_serve.build_prefill(cfg).lower(
        params, cache, jax.ShapeDtypeStruct((2, 16), jnp.int32), group,
        group).as_text()
    decode = llama_serve.build_decode_k(cfg).lower(
        params, cache, ints, ints, ints, ints, bools, bools, k=4,
        s_active=32).as_text()
    assert (_sha(prefill), _sha(decode)) == (prefill_sha, decode_sha)

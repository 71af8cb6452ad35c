"""Nemotron-H's shape at toy widths through the dense serving plane, held to
``benchmarks/references/nemotron_h_decoder.py`` (float32, the recurrence
token by token, a full causal softmax, every held expert on every token):
blocks of ONE sub-layer each, ``M E M * E M E`` -- so one layer of the walk
has no feed-forward half --, Mamba-2 with TWO groups of B and C and a gated
norm a group, 2 K/V heads without rotation, and two-matrix ``relu^2`` experts
in a latent narrower than the stream, top-3 of 16 with 4 held, beside a
full-width shared expert.

What it costs (this file alone, one worker, a cold cache): 69 test-seconds
for its 19 tests, over the 60 its issue hoped for: 16 s prefill + decode
through the cache, 11 s the engine's, 3-5 s each of the six variants (each
patches the program or a static field, so each is a program of its own to
compile; the two that change a number alone run on the chip tool only).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.lib import nemotron_flops
from benchmarks.references import nemotron_h_decoder as reference
from benchmarks.tools import nemotron_check
from ray_tpu.models import llama, llama_serve
from ray_tpu.models.llama import LlamaConfig

VOCAB, SLOTS, MAX_LEN = 256, 4, 64
PATTERN = "MEM*EME"
TOL = 1e-3          # float32 both sides: the order of sums alone


def _cfg(**kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=64, n_layers=4, n_heads=8,
        n_kv_heads=2, head_dim=8, intermediate_size=128,
        max_seq_len=MAX_LEN, norm_eps=1e-5, tie_embeddings=False,
        remat=False, dtype=jnp.float32, block_pattern=PATTERN, rope=False,
        ssm_heads=8, ssm_head_dim=8, ssm_state=16, ssm_groups=2, ssm_conv=4,
        ssm_chunk=8, moe_experts=16, moe_held=(4, 4), moe_top_k=3,
        moe_norm_topk=True, moe_intermediate_size=32, moe_shared_size=48,
        moe_latent_size=32, moe_activation="relu2",
        moe_router_score="sigmoid", moe_router_bias=True,
        moe_routed_scale=2.5, moe_dispatch_chunk=16)
    base.update(kw)
    return LlamaConfig(**base)


def _published(cfg, pattern=PATTERN):
    """The toy configuration in the published key names (what the
    reference and the yardstick read)."""
    first, held = cfg.held_experts
    return {
        "num_hidden_layers": len(pattern),
        "hybrid_override_pattern": pattern, "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size, "norm_eps": cfg.norm_eps,
        "tie_word_embeddings": False, "mamba_num_heads": cfg.ssm_heads,
        "mamba_head_dim": cfg.ssm_head_dim, "ssm_state_size": cfg.ssm_state,
        "n_groups": cfg.ssm_groups, "conv_kernel": cfg.ssm_conv,
        "mlp_hidden_act": "relu2", "moe_latent_size": cfg.moe_latent_size,
        "moe_intermediate_size": cfg.expert_width,
        "moe_shared_expert_intermediate_size": cfg.moe_shared_size,
        "n_shared_experts": 1, "n_routed_experts": held,
        "num_experts_per_tok": cfg.moe_top_k,
        "norm_topk_prob": cfg.moe_norm_topk,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "share": {"n_routed_experts_published": cfg.moe_experts,
                  "experts_first": first, "experts_held": held},
        "dtype": {"serve": "float32", "ssm_state": "float32"}}


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return (cfg, family.init_params(jax.random.key(7), cfg, jnp.float32),
            _published(cfg))


def _gap(params, prompt, emitted, published):
    return float(reference.teacher_forced_report(
        params, prompt, emitted, published)["gap"].max())


def _walk(cfg, params, tokens):
    return llama.layer_walk(
        params, jnp.asarray(tokens, jnp.int32), cfg,
        lambda q, k, v, pos, _cache: (
            llama.dot_attention(q, k, v, pos, cfg.attn_scale), (k, v)))[0]


# ------------------------------------------------ config, tree and cache
def test_the_config_its_parameters_and_its_cache(model):
    """The pattern's seven blocks are four layers of the walk, the second
    without a feed-forward half and so a stack of its own; a mixer's leaves
    are counted among the mixers, an expert part's among the expert parts;
    an expert has two matrices as wide as the latent."""
    cfg, params, published = model
    assert cfg.layer_types == ("mamba", "mamba", "attention", "mamba")
    assert [(key, part.period, part.n_layers, part.no_ffn, first)
            for part, key, first in cfg.parts()] == [
        ("layers", ("mamba",), 1, False, 0),
        ("layers_1", ("mamba",), 1, True, 1),
        ("layers_2", ("attention", "mamba"), 2, False, 2)]
    assert (cfg.layers_of("mamba"), cfg.attending_layers()) == (3, 1)
    assert not cfg.plain_decoder and not cfg.one_kv_stack
    assert llama.param_count(params) == nemotron_flops.parameters(published)
    bare, last = params["layers_1"], params["layers_2"]
    assert not {"mlp_norm", "router", "w_up", "ws_up", "w_lat_in"} & set(bare)
    assert bare["ssm_in"].shape == (1, 64, 64 + 64 + 2 * 2 * 16)
    assert "w_gate" not in last and "ws_gate" not in last
    assert last["w_up"].shape == (2, 4, 32, 32)
    assert last["w_down"].shape == (2, 4, 32, 32)
    assert last["w_lat_in"].shape == (2, 64, 32)
    assert last["ws_up"].shape == (2, 64, 48)
    assert last["router"].shape == (2, 64, 16)
    assert last["router_bias"].dtype == jnp.float32
    assert last["wk"].shape == (1, 64, 16) and last["ssm_dt"].shape[0] == 1
    axes = llama.param_logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(lambda x: 0, params)) \
        == jax.tree.structure(jax.tree.map(
            lambda x: 0, axes, is_leaf=lambda t: isinstance(t, tuple)))
    served = _cfg(dtype=jnp.bfloat16)
    cache = jax.eval_shape(lambda: llama_serve.init_cache(served, 3, 64))
    assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
        "k": ((1, 3, 64, 2, 8), "bfloat16"),
        "v": ((1, 3, 64, 2, 8), "bfloat16"),
        "ssm": ((3, 3, 16, 64), "float32"),
        "conv": ((3, 3, 3, 128), "bfloat16")}
    per_slot = nemotron_flops.slot_bytes(
        dict(published, dtype={"serve": "bfloat16", "ssm_state": "float32"}),
        64)
    pools = llama_serve.cache_pools(served, 3, 64)
    assert {k: v[0] for k, v in pools.items()} \
        == {k: 3 * v for k, v in per_slot.items()}
    assert llama_serve.share_and_state(served) == {
        "state_bytes_per_slot": per_slot["ssm"] + per_slot["conv"],
        "ssm_groups": 2, "experts_held": 4, "experts_routed": 16}
    with pytest.raises(NotImplementedError, match="served only"):
        llama.forward(None, jnp.zeros((1, 4), jnp.int32), cfg)


def test_two_kv_heads_of_a_whole_lane_row_are_stored_as_rows():
    """2 K/V heads of 128 do not fill a sublane tile by position: beside a
    state the pool is the rows the decode kernel reads, and the engine says
    ``kernel``; a plain decoder's pool stays by position."""
    wide = _cfg(head_dim=128, n_heads=4, dtype=jnp.bfloat16)
    assert wide.kv_as_rows and (wide.kv_row_heads, wide.kv_row_dim) == (2, 128)
    cache = jax.eval_shape(lambda: llama_serve.init_cache(wide, 3, 64))
    assert cache["k"].shape == (1, 3, 64 * 2, 128)
    assert llama_serve.kv_rows(wide, cache) == {
        "kv_row_heads": 2, "kv_row_dim": 128, "decode_attention": "kernel"}
    from ray_tpu.ops import decode_attention

    assert decode_attention._tiles(2, 128, as_rows=True)
    assert not decode_attention._tiles(2, 128)
    plain = LlamaConfig.debug(n_kv_heads=2, head_dim=128)
    assert plain.one_kv_stack and not plain.kv_as_rows


def test_config_refusals():
    with pytest.raises(ValueError, match="block_pattern"):
        _cfg(block_pattern="EM")              # an expert part with no mixer
    with pytest.raises(ValueError, match="block_pattern"):
        _cfg(block_pattern="M-M*", n_layers=3)            # a dense block
    with pytest.raises(ValueError, match="layer_types names each"):
        _cfg(n_layers=7)              # n_layers counts LAYERS, not blocks
    with pytest.raises(ValueError, match="in place of"):
        _cfg(layer_pattern=("mamba",))
    with pytest.raises(ValueError, match="ssm_groups"):
        _cfg(ssm_groups=3)
    with pytest.raises(ValueError, match="moe_experts"):
        _cfg(moe_experts=0, moe_held=())
    with pytest.raises(ValueError, match="moe_activation"):
        _cfg(moe_activation="gelu")
    # the config survives the copies the walks make of it
    cfg = _cfg()
    assert dataclasses.replace(cfg, dtype=jnp.bfloat16).layer_types \
        == cfg.layer_types


# ----------------------------------------------- engine against reference
def test_the_walk_is_the_reference_at_every_position(model):
    """Logits, every position of rows of 40 (five chunks of 8): the grouped
    chunked scan against the recurrence, the norm a group, the layer without
    an FFN, the latent experts beside the shared one."""
    cfg, params, published = model
    tokens = np.random.default_rng(1).integers(0, VOCAB, (2, 40))
    theirs = reference.logits(params, tokens, published)
    assert float(jnp.std(theirs)) > 0.3
    np.testing.assert_allclose(_walk(cfg, params, tokens), theirs, atol=2e-4)


def test_prefill_then_decode_through_the_cache_is_the_reference(model):
    """Three prompts of unlike lengths in ONE padded group, then decoded
    together through the cache, one sitting out a chunk in the middle: every
    emitted position of each within TOL of the reference's full forward
    pass; the prefill's own logits are the reference's numbers at each
    row's last position."""
    cfg, params, published = model
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32)
               for n in (1, 13, 30)]
    slots = (2, 0, 3)
    toks = np.zeros((4, 32), np.int32)
    for g, prompt in enumerate(prompts):
        toks[g, :len(prompt)] = prompt
    lengths = jnp.asarray([1, 13, 30, 0], jnp.int32)
    got = llama.prefill_with_states(params, jnp.asarray(toks), lengths, cfg)
    want = reference.logits(params, toks[:3], published)
    for g, n in enumerate((1, 13, 30)):
        assert float(jnp.abs(got[0][g] - want[g, n - 1]).max()) <= TOL
    state, conv = got[4]
    assert state.shape == (3, 4, 16, 64) and conv.shape == (3, 3, 4, 128)
    assert float(jnp.abs(state[:, 3]).max()) == 0.0      # the padding row

    cache = llama_serve.init_cache(cfg, SLOTS, MAX_LEN)
    cache, first, load = family.prefill(cfg, params, cache, prompts, slots)
    # held + elsewhere = the real positions' picks, in every EXPERT block
    assert np.asarray(load[0]).shape == (3, 4)
    assert int(np.asarray(load[0]).sum() + np.asarray(load[2])) \
        == 44 * cfg.moe_top_k * PATTERN.count("E")
    tok, lens = family.seat(first, (1, 13, 30), slots)
    emitted = {s: [int(t)] for s, t in zip(slots, first)}
    for who in (slots, (2, 3), slots, slots):
        cache, out, tok, lens, _load = family.decode(cfg, params, cache, tok,
                                                     lens, who)
        for s in who:
            emitted[s] += [int(t) for t in out[:, s]]
    assert [len(emitted[s]) for s in slots] == [17, 13, 17]
    for prompt, s in zip(prompts, slots):
        assert _gap(params, prompt, emitted[s], published) <= TOL


def test_a_reused_slot_inherits_nothing(model):
    cfg, params, published = model
    family.reused_slot_inherits_nothing(
        lambda prompt, n, cache=None: family.serve_one(
            cfg, params, prompt, n, cache=cache),
        lambda prompt, tokens: _gap(params, prompt, tokens, published), TOL)


@pytest.mark.parametrize("variant", [
    v for v in nemotron_check.VARIANTS
    if v not in ("no_routed_scale", "float8_weights")])
def test_a_broken_variant_fails_the_reference(model, variant):
    """The same weights under a program that is wrong in one place
    (``benchmarks/tools/nemotron_check.py`` runs these and two more at the
    published widths on the chip), LOGITS against the reference's at every
    position of a 32-token row, 24 prefilled and 8 through the cache: a
    bfloat16 state, a recurrence run in bfloat16, the gated norm over the
    whole width, relu for relu^2 and one group's B and C for all heads each
    read over the tolerance the intact program holds.  (A dropped routed
    scale is a wrong number in the intact program's own comparison, and the
    share test's.)"""
    cfg, params, published = model
    tokens = np.random.default_rng(2).integers(0, VOCAB, (1, 32))
    vcfg, patched = nemotron_check.broken(variant, cfg)
    with patched():
        distance = nemotron_check.logit_distance(
            vcfg, nemotron_check.variant_weights(variant, params), tokens,
            published, prompt=24, max_len=MAX_LEN, reference_params=params)
    # read here: intact 3.7e-6 of a deviation; the bfloat16 state, the
    # mildest (8 decoded steps round it 8 times), 2.4e-3; the others 1.5-3.1
    if variant == "intact":
        assert distance <= TOL / 10
    else:
        assert distance > TOL, distance


# ----------------------------------------------------------- the share
def test_the_four_shares_add_up_to_the_uncut_layer(model):
    """The routed parts that the four chips of a layer compute, each on
    its own quarter of the 16 experts, through ``W_2`` (which is linear),
    plus the shared expert counted ONCE, are what the uncut layer gives: the
    program's expert part at each ``moe_held`` against the reference's with
    every expert held."""
    cfg, params, published = model
    layer = {k: v[0] for k, v in params["layers"].items()}
    keys = jax.random.split(jax.random.key(11), 3)
    whole = {**layer,
             "w_up": jax.random.normal(keys[0], (16, 32, 32)) * 32 ** -0.5,
             "w_down": jax.random.normal(keys[1], (16, 32, 32)) * 32 ** -0.5}
    x = jax.random.normal(keys[2], (2, 5, 64))

    def part(first, count, layer):
        """x -> the expert part's output alone, of a chip that holds
        experts ``first .. first + count``."""
        c = dataclasses.replace(cfg, moe_held=(first, count))
        held = {**layer, "w_up": whole["w_up"][first:first + count],
                "w_down": whole["w_down"][first:first + count]}
        return llama.ffn_half(x, held, c)[0] - x

    no_shared = {**whole, "ws_up": jnp.zeros_like(whole["ws_up"])}

    def routed_part(first, count):
        return part(first, count, no_shared)

    shares = sum(routed_part(4 * rank, 4) for rank in range(4))
    shared_once = part(0, 16, whole) - routed_part(0, 16)
    uncut = reference._experts(
        x.reshape(10, 64), {k: whole[k] for k in reference.EXPERT_LEAVES},
        cfg.norm_eps, 0, cfg.moe_top_k, True, cfg.moe_routed_scale)[0] \
        - x.reshape(10, 64)
    np.testing.assert_allclose((shares + shared_once).reshape(10, 64), uncut,
                               atol=2e-5)
    # and a share is not the whole: the picks land on all four
    assert float(jnp.abs(routed_part(0, 4)).max()) > 1e-3
    assert float(jnp.abs(shares - routed_part(0, 4)).max()) > 1e-3


# ------------------------------------------------------------- the engine
_presets = family.presets({"nemotron_h_toy": _cfg})
engine = family.engines("nemotron_h_toy", max_slots=2, max_len=MAX_LEN,
                        prefill_groups=(1, 2))


def test_llm_server_serves_the_model_and_says_what_it_holds(
        model, traced, engine):
    cfg, params, published = model
    # two slots and groups of one and two rows: of three requests the third
    # is served in a REUSED slot.  A server of its own: every chunk on the
    # timeline is counted, and it is shut down before they are.
    server = engine(params=params, fresh=True)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, VOCAB, n).tolist() for n in (9, 20, 31)]
    replies = family.generate(
        server, [{"prompt": p, "max_new_tokens": 10} for p in prompts])
    pools = server.kv_stats()
    server.shutdown()
    for prompt, reply in zip(prompts, replies):
        assert len(reply["tokens"]) == 10
        assert _gap(params, prompt, reply["tokens"], published) <= TOL
    events = traced.export_timeline()
    state = 3 * 16 * 64 * 4
    (build,) = family.span_args(events, "serve.engine_build")
    assert {k: v for k, v in build.items() if not k.endswith("_id")} == {
        "kv_row_heads": 2, "kv_row_dim": 8, "decode_attention": "kernel",
        "state_bytes_per_slot": state + 3 * 3 * 128 * 4, "ssm_groups": 2,
        "experts_held": 4, "experts_routed": 16}
    chunks = family.span_args(events, "serve.chunk")
    assert chunks
    for c in chunks:
        assert c["state_rows_updated"] == c["k"] * c["active"]
        # held + elsewhere: every active slot's picks in the 3 expert blocks
        assert c["expert_rows"] + c["expert_rows_elsewhere"] \
            == c["state_rows_updated"] * cfg.moe_top_k * 3
    assert pools["state_pool"]["bytes_per_slot"]["ssm"] == state
    groups = family.span_args(events, "serve.prefill_group")
    assert groups and all(g["expert_rows"] >= 0 and g["scan_chunks"] > 0
                          for g in groups)


@pytest.mark.parametrize("plane,args", family.PLANES)
def test_the_planes_that_cannot_hold_a_state_refuse_it(plane, args):
    family.refuses_plane("nemotron_h_toy", plane, args, "state-space",
                         words=("state",))

"""Solar-Open2's shape at toy widths through the dense serving plane, held to
``benchmarks/references/solar_open2_decoder.py`` (float32, the delta rule
token by token, a full causal softmax, every held expert on every token):
one period ``A K K K`` -- a gated NoPE attention layer and three KDA layers
--, every FFN a shared expert beside a held range of sigmoid-routed experts
with a selection bias.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.lib import kda_flops
from benchmarks.references import solar_open2_decoder as reference
from benchmarks.tools import kda_check
from ray_tpu.models import llama, llama_serve
from ray_tpu.models.llama import LlamaConfig

VOCAB, SLOTS, MAX_LEN = 256, 4, 64
TOL = 1e-3          # float32 both sides: the order of sums alone


def _cfg(**kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=64, n_layers=4, n_heads=8,
        n_kv_heads=2, head_dim=8, intermediate_size=128,
        max_seq_len=MAX_LEN, norm_eps=1e-5, tie_embeddings=False,
        remat=False, dtype=jnp.float32,
        layer_pattern=("attention", "kda", "kda", "kda"), rope=False,
        attn_gate=True, kda_heads=4, kda_head_dim=16, kda_conv=4,
        kda_gate_rank=8, kda_chunk=8, moe_experts=16, moe_held=(4, 4),
        moe_top_k=4, moe_norm_topk=True, moe_intermediate_size=32,
        moe_shared_size=32, moe_router_score="sigmoid",
        moe_router_bias=True, moe_dispatch_chunk=16)
    base.update(kw)
    return LlamaConfig(**base)


def _published(cfg):
    """The toy configuration in the published key names (what the
    reference and the yardstick read)."""
    first, held = cfg.held_experts
    return {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "intermediate_size": cfg.intermediate_size,
        "tie_word_embeddings": False, "gqa_layers": [0, 4, 8],
        "linear_attn_config": {"short_conv_kernel_size": cfg.kda_conv,
                               "head_dim": cfg.kda_head_dim,
                               "num_heads": cfg.kda_heads},
        "kda_gate_rank": cfg.kda_gate_rank,
        "moe_intermediate_size": cfg.expert_width, "n_shared_experts": 1,
        "n_routed_experts": held, "num_experts_per_tok": cfg.moe_top_k,
        "norm_topk_prob": cfg.moe_norm_topk,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "share": {"n_routed_experts_published": cfg.moe_experts,
                  "experts_first": first, "experts_held": held},
        "dtype": {"serve": "float32", "kda_state": "float32"}}


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return (cfg, family.init_params(jax.random.key(7), cfg, jnp.float32),
            _published(cfg))


def _gap(params, prompt, emitted, published):
    return float(reference.teacher_forced_report(
        params, prompt, emitted, published)["gap"].max())


# ------------------------------------------------ config, tree and cache
def test_the_config_its_parameters_and_its_cache(model):
    cfg, params, published = model
    assert cfg.period == ("attention", "kda", "kda", "kda")
    assert (cfg.layers_of("kda"), cfg.attending_layers()) == (3, 1)
    assert not cfg.plain_decoder and not cfg.one_kv_stack
    assert llama.param_count(params) == kda_flops.parameters(published)
    layers = params["layers"]
    assert layers["w_attn_gate"].shape == (1, 64, 64)
    assert layers["kda_qkv"].shape == (3, 64, 3 * 64)
    assert layers["kda_low"].shape == (3, 64, 2 * 8 + 4)
    assert layers["w_gate"].shape == (4, 4, 64, 32)
    assert layers["router"].shape == (4, 64, 16)
    assert layers["router_bias"].dtype == jnp.float32
    served = _cfg(dtype=jnp.bfloat16)
    cache = jax.eval_shape(lambda: llama_serve.init_cache(served, 3, 64))
    assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
        "k": ((1, 3, 64, 2, 8), "bfloat16"),
        "v": ((1, 3, 64, 2, 8), "bfloat16"),
        "ssm": ((3, 3, 4, 16, 16), "float32"),
        "conv": ((3, 3, 3, 192), "bfloat16")}
    per_slot = kda_flops.slot_bytes(
        dict(published, dtype={"serve": "bfloat16", "kda_state": "float32"}),
        64)
    pools = llama_serve.cache_pools(served, 3, 64)
    assert {k: v[0] for k, v in pools.items()} \
        == {k: 3 * v for k, v in per_slot.items()}
    assert llama_serve.state_bytes_per_slot(served) \
        == {k: per_slot[k] for k in ("ssm", "conv")}
    with pytest.raises(NotImplementedError, match="served only"):
        llama.forward(None, jnp.zeros((1, 4), jnp.int32), cfg)


# ----------------------------------------------- engine against reference
def test_the_walk_is_the_reference_at_every_position(model):
    """Logits, every position of rows of 40 (five chunks of 8): the chunked
    rule against the recurrence, the gate on the attending layer, the held
    experts beside the shared one."""
    cfg, params, published = model
    tokens = np.random.default_rng(1).integers(0, VOCAB, (2, 40))
    mine = llama.layer_walk(
        params, jnp.asarray(tokens, jnp.int32), cfg,
        lambda q, k, v, pos, _cache: (
            llama.dot_attention(q, k, v, pos, cfg.attn_scale), (k, v)))[0]
    theirs = reference.logits(params, tokens, published)
    assert float(jnp.std(theirs)) > 0.3
    np.testing.assert_allclose(mine, theirs, atol=2e-4)


def test_prefill_then_decode_through_the_cache_is_the_reference(model):
    """Three prompts of unlike lengths in ONE padded group (lengths inside
    a chunk, a padding row behind them), then decoded together through the
    cache, one sitting out a chunk in the middle: every emitted position
    of each within TOL of the reference's full forward pass; the prefill's
    own logits are the reference's numbers at each row's last position."""
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
    assert state.shape == (3, 4, 4, 16, 16) and conv.shape == (3, 3, 4, 192)
    assert float(jnp.abs(state[:, 3]).max()) == 0.0      # the padding row

    cache = llama_serve.init_cache(cfg, SLOTS, MAX_LEN)
    cache, first, load = family.prefill(cfg, params, cache, prompts, slots)
    # held + elsewhere = the real positions' picks, in every layer
    assert int(np.asarray(load[0]).sum() + np.asarray(load[2])) \
        == 44 * cfg.moe_top_k * cfg.n_layers
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


@pytest.mark.parametrize("variant", kda_check.VARIANTS[:5])
def test_a_broken_variant_fails_the_reference(model, variant):
    """The same weights under a program that is wrong in one place
    (``benchmarks/tools/kda_check.py`` runs the same variants at the
    published widths on the chip), LOGITS against the reference's at every
    position of a 32-token row, 24 prefilled and 8 through the cache: a bfloat16 state, a
    beta without its 2, the decay after the correction and a dropped
    attention gate each read over the tolerance the intact program holds."""
    cfg, params, published = model
    tokens = np.random.default_rng(2).integers(0, VOCAB, (1, 32))
    vcfg, patched = kda_check.broken(variant, cfg)
    with patched():
        distance = kda_check.logit_distance(
            vcfg, kda_check.variant_weights(variant, params), tokens,
            published, prompt=24, max_len=MAX_LEN, reference_params=params)
    if variant == "intact":
        assert distance <= TOL
    else:
        assert distance > 10 * TOL, distance


# ------------------------------------------------------------- the engine
_presets = family.presets({"solar_open2_toy": _cfg})
engine = family.engines("solar_open2_toy", max_slots=2, max_len=MAX_LEN,
                        prefill_groups=(1, 2))


def test_llm_server_serves_the_model_and_counts_the_state_it_moves(
        model, traced, monkeypatch, engine):
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
    chunks = family.span_args(events, "serve.chunk")
    assert chunks
    state = 3 * 4 * 16 * 16 * 4
    for c in chunks:
        assert c["kda_slots_advanced"] == c["k"] * c["active"] \
            == c["state_rows_updated"]
        assert c["kda_state_bytes"] == 2 * c["kda_slots_advanced"] * state
        assert c["expert_rows"] > 0
    assert pools["state_pool"]["bytes_per_slot"]["ssm"] == state
    # a toy state (d = 16) keeps XLA's chunked rule: no position went
    # through ``ops/kda_chunk.py``; at a state the kernel takes, a launch's
    # padded positions (whole blocks of chunks) x the three KDA layers
    groups = family.span_args(events, "serve.prefill_group")
    assert groups and all(g["kda_chunk_positions"] == 0 for g in groups)
    from ray_tpu.ops import kda_chunk

    monkeypatch.setattr(kda_chunk, "engages", lambda d, chunk: True)
    server._record_prefill_group(0.0, 1.0, 20, np.array([9, 20]), 2)
    newest = family.span_args(traced.export_timeline(),
                              "serve.prefill_group")[-1]
    assert newest["kda_chunk_positions"] == 2 * 24 * 3
    assert newest["token_positions"] == 2 * 20
    family.refuses_plane("solar_open2_toy", "paged", dict(paged=True),
                         "linear-attention")

"""DeepSeek-V2's shape at toy widths through the dense serving plane, held
to ``benchmarks/references/deepseek_v2_decoder.py`` (float32, expanded
form only, no cache, every held expert on every token):

- prefill (expanded) then decode (absorbed, through the latent cache and
  ``mla_decode_attention``) against the reference's full forward pass at
  every position, through the masked einsum and the flash forward;
- absorbed = expanded on the same rows; a reused slot inherits nothing;
- the broken variants of ``benchmarks/tools/mla_check.py`` each FAIL;
- ``mla_decode_attention`` (interpreted) against the masked einsum across
  block boundaries and ragged lengths;
- group-limited routing and YaRN's table against by-hand values;
- THE SHARE TEST: the four ranks' partial results of one expert layer, the
  shared expert counted once, sum to the uncut reference's layer;
- config refusals, the planes that refuse the model, ``LLMServer.generate``
  end to end, spans / counters / the latent pool present for this model
  and absent for the others.
"""

import dataclasses
import functools
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.references import deepseek_v2_decoder as reference
from benchmarks.tools import mla_check
from ray_tpu.models import llama, llama_serve, moe
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import metrics, timeline, tracing
from ray_tpu.ops import mla_decode_attention as kernel_module

VOCAB, SLOTS, MAX_LEN = 256, 4, 64
TOL = 1e-3          # float32 both sides: the order of sums alone
YARN = {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
        "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 16}


def _cfg(**kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=64, n_layers=3, n_heads=4,
        n_kv_heads=4, head_dim=24, intermediate_size=128,
        max_seq_len=MAX_LEN, rope_theta=10000.0, norm_eps=1e-6,
        tie_embeddings=False, remat=False, dtype=jnp.float32,
        kv_lora_rank=32, q_lora_rank=48, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_scaling=YARN,
        first_dense_layers=1, moe_experts=16, moe_top_k=3,
        moe_norm_topk=False, moe_intermediate_size=32, moe_shared_size=64,
        moe_groups=4, moe_top_groups=2, moe_routed_scale=16.0,
        moe_held=(0, 8))
    base.update(kw)
    return LlamaConfig(**base)


def _published(cfg, first=None):
    """The toy configuration in the published key names (what the
    reference reads)."""
    held_first, held = cfg.held_experts
    return {
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim,
        "v_head_dim": cfg.v_head_dim, "kv_lora_rank": cfg.kv_lora_rank,
        "q_lora_rank": cfg.q_lora_rank, "rms_norm_eps": cfg.norm_eps,
        "rope_theta": cfg.rope_theta,
        "rope_scaling": dict(cfg.rope_scaling) if cfg.rope_scaling else None,
        "moe_intermediate_size": cfg.expert_width,
        "n_group": cfg.moe_groups, "topk_group": cfg.moe_top_groups,
        "num_experts_per_tok": cfg.moe_top_k,
        "routed_scaling_factor": cfg.moe_routed_scale,
        "norm_topk_prob": cfg.moe_norm_topk,
        "first_k_dense_replace": cfg.first_dense_layers,
        "n_routed_experts": held, "tie_word_embeddings": False,
        "topk_method": "group_limited_greedy", "scoring_func": "softmax",
        "share": {"experts_first": held_first if first is None else first}}


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
    (1, 6), (5, 24), (16, 12), (21, 21), (40, 17)])
def test_prefill_then_decode_through_the_latent_cache(
        model, monkeypatch, flash, prompt_len, new_tokens):
    """Logits at every emitted position: the prefill attends expanded,
    the decode absorbed, over rows the prefill and earlier steps wrote."""
    cfg, params = model
    if flash:
        monkeypatch.setattr(llama, "FLASH_PREFILL_FROM", 0)
    prompt = np.random.default_rng(prompt_len).integers(
        0, VOCAB, prompt_len).astype(np.int32)
    # one case keeps its own bucket: 16 rows in a bucket of 16, the row
    # that fills its bucket exactly (no padding position behind the last
    # token); every other length is data in the file's one bucket
    bucket = 16 if prompt_len == 16 else None
    emitted, _ = family.serve_one(cfg, params, prompt, new_tokens,
                                  bucket=bucket)
    assert _gap(cfg, params, prompt, emitted) <= TOL


def test_the_head_groups_of_a_long_prefill_are_the_heads(monkeypatch):
    """A prompt past ``FLASH_PREFILL_FROM`` expands and attends a group
    of heads at a time, and dispatches to its experts a chunk of positions
    at a time: the same logits and rows as all at once."""
    cfg = _cfg(n_layers=2)
    params = family.init_params(jax.random.key(3), cfg)
    prompt = np.random.default_rng(0).integers(0, VOCAB, (1, 48))
    lengths = jnp.asarray([41], jnp.int32)
    whole = llama.prefill_with_states(params, jnp.asarray(prompt), lengths,
                                      cfg)
    monkeypatch.setattr(llama, "FLASH_PREFILL_FROM", 16)
    monkeypatch.setattr(llama, "LATENT_HEAD_GROUP", 2)
    grouped = llama.prefill_with_states(
        params, jnp.asarray(prompt), lengths,
        dataclasses.replace(cfg, moe_dispatch_chunk=16))
    assert float(jnp.abs(whole[0] - grouped[0]).max()) < 1e-4
    assert float(jnp.abs(whole[1] - grouped[1]).max()) < 1e-4
    assert whole[2] is None and grouped[2] is None
    assert (np.asarray(whole[3]) == np.asarray(grouped[3])).all()
    assert whole[1].shape == (2, 1, 48, cfg.latent_row)


def test_absorbed_is_expanded(model):
    """One layer's attention at the LAST position of a row, computed as
    heads of their own keys and values and as every head's ``[q~ ; q_rope]``
    against the latent rows: the same mathematics."""
    cfg, params = model
    layer = {k: v[1] for k, v in params["layers"].items()}
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 13, 64)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(13)[None], (2, 13))
    sin, cos = llama.rope_table(positions, cfg.rope_dim, cfg.rope_theta,
                                cfg.rope_scaling)
    cq, latent = llama.latent_down(x, layer, sin, cos, cfg)
    expanded = llama.latent_attend_expanded(
        cq, latent, layer, sin, cos, cfg,
        lambda q, k, v: llama.dot_attention(q, k, v, positions,
                                            cfg.attn_scale))
    assert expanded.shape == (2, 13, 4, 16)
    q_nope, q_rope = llama.latent_queries(
        cq, llama._wq_b_heads(layer, cfg), sin, cos, cfg)
    q = llama.latent_absorb_query(q_nope[:, -1], q_rope[:, -1], layer, cfg)
    assert q.shape == (2, 4, cfg.latent_row)
    pool = jnp.zeros((3, 2, 16, cfg.latent_row)).at[1, :, :13].set(latent)
    u = kernel_module.mla_decode_attention(
        q, pool, jnp.int32(1), jnp.asarray([12, 12]), jnp.asarray([True] * 2),
        s_active=16, scale=cfg.attn_scale, v_width=cfg.kv_lora_rank)
    absorbed = llama.latent_absorb_values(u, layer, cfg)
    assert float(jnp.abs(absorbed - expanded[:, -1]).max()) < 1e-5


def test_a_reused_slot_inherits_nothing(model):
    cfg, params = model
    family.reused_slot_inherits_nothing(
        functools.partial(family.serve_one, cfg, params),
        functools.partial(_gap, cfg, params), TOL)


@pytest.mark.parametrize("variant", mla_check.VARIANTS)
def test_a_broken_variant_fails_the_reference(model, variant):
    """What the comparison is there to catch, each with the same weights
    (``benchmarks/tools/mla_check.py`` runs the same variants at the
    published widths on the chip); the intact program passes."""
    cfg, params = model
    prompt = np.random.default_rng(11).integers(0, VOCAB, 21).astype(
        np.int32)
    vcfg, patched = mla_check.broken(variant, cfg)
    with patched:
        emitted = mla_check.serve_one(vcfg, params, prompt, 24, 32, MAX_LEN,
                                      k=4, slots=SLOTS, slot=2)
    family.reads_as(_gap(cfg, params, prompt, emitted), variant, TOL)


# ------------------------------------------------------------- the kernel
@pytest.mark.parametrize("block", [4, 8, 64])
def test_mla_decode_attention_is_the_masked_einsum(monkeypatch, block):
    """Blocks of 4 and 8 over 20 positions: rows that end inside a block,
    on a block's edge, in the last block (moved back inside the cache);
    an inactive row, an empty row, a row past ``s_active``."""
    monkeypatch.setattr(kernel_module, "BLOCK_K", block)
    rng = np.random.default_rng(2)
    L, B, S, H, W, V = 3, 7, 20, 5, 40, 32
    pool = jnp.asarray(rng.normal(size=(L, B, S, W)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    lens = jnp.asarray([0, 3, 7, 8, 19, 25, 11], jnp.int32)
    active = jnp.asarray([True, True, True, True, True, True, False])
    for s_active in (20, 16):
        got = kernel_module.mla_decode_attention(
            q, pool, jnp.int32(2), lens, active, s_active=s_active,
            scale=0.3, v_width=V)
        n = np.where(np.asarray(active),
                     np.minimum(np.asarray(lens) + 1, s_active), 0)
        s = np.einsum("bhw,bsw->bhs", np.asarray(q), np.asarray(pool[2])) \
            * 0.3
        s = np.where(np.arange(S)[None, None] < n[:, None, None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True, initial=-1e30))
        p = p / np.maximum(p.sum(-1, keepdims=True), 1e-30)
        want = np.einsum("bhs,bsv->bhv", p, np.asarray(pool[2, :, :, :V]))
        want = np.where((n > 0)[:, None, None], want, 0.0)
        assert got.shape == (B, H, V)
        assert float(np.abs(np.asarray(got) - want).max()) < 1e-5
        twin = kernel_module._xla_decode_attention(
            q, pool, jnp.int32(2), jnp.asarray(n), s_active, 0.3, V)
        assert float(np.abs(np.asarray(twin) - want).max()) < 1e-5


def test_stale_rows_past_a_slots_length_change_nothing():
    rng = np.random.default_rng(3)
    pool = jnp.asarray(rng.normal(size=(1, 2, 16, 24)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 3, 24)), jnp.float32)
    lens, active = jnp.asarray([4, 9]), jnp.asarray([True, True])
    args = dict(s_active=16, scale=0.5, v_width=16)
    clean = kernel_module.mla_decode_attention(q, pool, 0, lens, active,
                                               **args)
    dirty = pool.at[0, 0, 5:].set(jnp.nan).at[0, 1, 10:].set(jnp.inf)
    assert (np.asarray(kernel_module.mla_decode_attention(
        q, dirty, 0, lens, active, **args)) == np.asarray(clean)).all()


# ----------------------------------------------------- routing and YaRN
def test_group_limited_routing_by_hand():
    """8 experts in 4 groups of 2, the 2 best groups (0 and 1) stay, top-3
    of what stays, gates as they are x 2.  The plain top-3 is experts 0, 2
    and 6; expert 6's group scores third, so the group-limited third is
    expert 1, at 0.02."""
    logits = jnp.log(jnp.asarray(
        [[0.30, 0.02, 0.15, 0.01, 0.12, 0.13, 0.14, 0.13]]))
    router = jnp.eye(8)
    probs, gates, idx = moe._route(logits, router, 3, False, 4, 2, 2.0)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1, 2]
    want = {0: 0.60, 2: 0.30, 1: 0.04}
    for e, g in zip(np.asarray(idx[0]), np.asarray(gates[0])):
        assert g == pytest.approx(want[int(e)], rel=1e-5)
    assert float(probs.sum()) == pytest.approx(1.0)
    _, plain, plain_idx = moe._route(logits, router, 3, False)
    assert sorted(np.asarray(plain_idx[0]).tolist()) == [0, 2, 6]
    _, normed, _ = moe._route(logits, router, 3, True, 4, 2, 2.0)
    assert float(normed.sum()) == pytest.approx(2.0)
    # the reference's own gates, written apart, agree
    g, chosen = reference._gates(probs, 4, 2, 3, 2.0, False)
    assert sorted(np.asarray(chosen[0]).tolist()) == [0, 1, 2]
    assert float(g[0, 1]) == pytest.approx(0.04, rel=1e-5)
    assert float(g.sum()) == pytest.approx(0.94, rel=1e-5)


def test_yarn_table_by_hand():
    """DeepSeek-V2's published block at a 64-wide rope part: the
    correction range (10, 23), the softmax scale 0.11472, the blended
    frequencies, cos and sin times 1."""
    scaling = {**YARN, "original_max_position_embeddings": 4096}
    assert llama.yarn_correction_range(scaling, 64, 10000.0) == (10, 23)
    assert 64 * math.log(4096 / (32 * 2 * math.pi)) \
        / (2 * math.log(10000)) == pytest.approx(10.47, abs=0.01)
    freqs, factor = llama.rope_frequencies(64, 10000.0, scaling)
    assert factor == pytest.approx(1.0)
    base = 10000.0 ** (-np.arange(32) / 32)
    assert np.allclose(freqs[:11], base[:11], rtol=1e-6)     # kept
    assert np.allclose(freqs[23:], base[23:] / 40, rtol=1e-6)  # stretched
    keep = 1 - (16 - 10) / 13
    assert freqs[16] == pytest.approx(
        base[16] / 40 * (1 - keep) + base[16] * keep, rel=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    cfg = _cfg(head_dim=192, qk_nope_head_dim=128, qk_rope_head_dim=64,
               rope_scaling=scaling)
    assert cfg.attn_scale == pytest.approx(0.11472, abs=1e-5)
    assert cfg.attn_scale == pytest.approx(192 ** -0.5 * m * m)
    # the reference's own table, written apart, is the same
    theirs, their_factor, their_m = reference._yarn(64, 10000.0, scaling)
    assert np.allclose(theirs, freqs, rtol=1e-6)
    assert (their_factor, their_m) == (pytest.approx(1.0), pytest.approx(m))
    # plain RoPE where there is no block, and a table that uses it
    plain, one = llama.rope_frequencies(64, 10000.0)
    assert one == 1.0 and np.allclose(plain, base, rtol=1e-6)
    sin, cos = llama.rope_table(jnp.asarray([[3]]), 64, 10000.0,
                                tuple(sorted(scaling.items())))
    assert np.allclose(np.asarray(sin[0, 0]), np.sin(3 * freqs), atol=1e-6)
    assert np.allclose(np.asarray(cos[0, 0]), np.cos(3 * freqs), atol=1e-6)


# ------------------------------------------------------------- the share
def _solar_cfg(**kw):
    """Solar-Open2's expert layer at toy widths: a sigmoid router with a
    selection bias, normalised gates, one shared expert."""
    return LlamaConfig(**{**dict(
        vocab_size=VOCAB, hidden_size=64, n_layers=4, n_heads=8,
        n_kv_heads=2, head_dim=8, intermediate_size=128,
        max_seq_len=MAX_LEN, norm_eps=1e-5, tie_embeddings=False,
        remat=False, dtype=jnp.float32,
        layer_pattern=("attention", "kda", "kda", "kda"), rope=False,
        attn_gate=True, kda_heads=4, kda_head_dim=16, kda_gate_rank=8,
        kda_chunk=8, moe_experts=16, moe_top_k=4, moe_norm_topk=True,
        moe_intermediate_size=32, moe_shared_size=32,
        moe_router_score="sigmoid", moe_router_bias=True), **kw})


def _uncut_deepseek(x, params, K):
    return reference._ffn(
        x, {k: params["layers"][k] for k in (
            "mlp_norm", "router", "w_gate", "w_up", "w_down", "ws_gate",
            "ws_up", "ws_down")},
        jnp.int32(0), 1e-6, False, 0, 4, 2, K, 16.0, False, 32)


def _uncut_solar(x, params, K):
    from benchmarks.references import solar_open2_decoder

    return solar_open2_decoder._ffn(
        x, {k: v[0] for k, v in params["layers"].items() if k in (
            "mlp_norm", "router", "router_bias", "w_gate", "w_up", "w_down",
            "ws_gate", "ws_up", "ws_down")}, 1e-5, 0, K, True, 1.0)[0]


@pytest.mark.parametrize("make,ranks,uncut_layer", [
    (_cfg, 4, _uncut_deepseek), (_solar_cfg, 8, _uncut_solar)],
    ids=["deepseek-v2", "solar-open2-250b"])
def test_the_four_shares_of_an_expert_layer_sum_to_the_uncut_layer(
        make, ranks, uncut_layer):
    """16 experts over 4 ranks of 4 (a routing group each; DeepSeek-V2) or
    8 ranks of 2 (Solar-Open2: sigmoid scores, a selection bias, normalised
    gates): every rank routes over all 16 and adds its own experts' part;
    the parts, the shared expert and the stream counted once, are the uncut
    reference's layer.  And the rows: held + elsewhere = tokens x top-k on
    every rank, the held ones summing to it over the ranks."""
    whole = make(moe_held=())
    held = 16 // ranks
    params = family.init_params(jax.random.key(5), whole)
    full = {k: v[0] for k, v in params["layers"].items()}
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(2, 11, 64)), jnp.float32)
    T, K = 22, whole.moe_top_k
    h = llama.rms_norm(x, full["mlp_norm"], whole.norm_eps)
    shared = (jax.nn.silu(h @ full["ws_gate"]) * (h @ full["ws_up"])) \
        @ full["ws_down"]
    routed, held_rows = 0.0, 0
    for rank in range(ranks):
        cfg = make(moe_held=(held * rank, held))
        layer = {k: (v[held * rank:held * (rank + 1)]
                     if k in llama.EXPERT_STACKS else v)
                 for k, v in full.items()}
        out, _aux, rows = llama.ffn_half(x, layer, cfg)
        assert rows.shape == (held + 1,)
        assert int(rows.sum()) == T * K          # held + elsewhere
        held_rows += int(rows[:held].sum())
        routed = routed + (out - x - shared)
    assert held_rows == T * K
    uncut = uncut_layer(x.reshape(T, 64), params, K)
    ours = (x + shared + routed).reshape(T, 64)
    assert float(jnp.abs(ours - uncut).max()) < 1e-4
    # the uncut program is that layer too
    out, _aux, rows = llama.ffn_half(x, full, whole)
    assert float(jnp.abs(out.reshape(T, 64) - uncut).max()) < 1e-4
    assert rows.shape == (16,) and int(rows.sum()) == T * K


@pytest.mark.parametrize("program,ragged_dots,conds,sha", [
    ("prefill", 3, 0, "7e7c61cf5af878b4"),
    ("decode_k", 3, 10, "3643d1604ed59a9b")], ids=["prefill", "decode_k"])
def test_serving_a_quarter_share_lowers_no_compact_dispatch(
        program, ragged_dots, conds, sha):
    """A share that TRAINING dispatches compactly (4 of 16 experts: twice
    their even share is half the assignments, ``moe.compact_rows``) is
    served by the programs the parent commit (47a1d40, PR 57) lowered: as
    many grouped matmuls, no conditional more, the same text by sha256
    (``decode_k``'s since PR 61: its 4 slots x top-3 = 12 sorted rows are
    gathered as 16, whole sublane tiles; cell 8's 32 x 6 are whole already;
    both since PR 63: a token's k-th result is gathered straight into the
    sum, by the one path every configuration takes, where a share un-sorted
    all its rows and reshaped them to (T, K x D) first).
    The loop over blocks exists where ``training`` asks for it alone."""
    import hashlib
    import re

    cfg = _cfg(moe_held=(4, 4))
    shapes = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    params = shapes(jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0)))
    cache = shapes(jax.eval_shape(
        lambda: llama_serve.init_cache(cfg, 4, 64)))
    group = jax.ShapeDtypeStruct((2,), jnp.int32)
    ints = jax.ShapeDtypeStruct((4,), jnp.int32)
    bools = jax.ShapeDtypeStruct((4,), jnp.bool_)
    traced = llama_serve.build_prefill(cfg).trace(
        params, cache, jax.ShapeDtypeStruct((2, 16), jnp.int32), group,
        group) if program == "prefill" else \
        llama_serve.build_decode_k(cfg).trace(
            params, cache, ints, ints, ints, ints, bools, bools, k=4,
            s_active=32)

    def count(jaxpr):
        text = str(jaxpr)
        return tuple(len(re.findall(rf"\b{op}\[", text))
                     for op in ("ragged_dot_general", "cond", "while"))

    assert count(traced.jaxpr)[:2] == (ragged_dots, conds)
    assert hashlib.sha256(traced.lower().as_text().encode()
                          ).hexdigest()[:16] == sha
    # the same layer as the train step walks it: the three matmuls inside
    # the loop over blocks of 48 of the 96 sorted rows, which serving has not
    layer = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         params["layers"])
    x = jax.ShapeDtypeStruct((2, 16, 64), cfg.dtype)
    assert moe.compact_rows(32, llama.expert_config(cfg)) == 48
    trained, served = (count(jax.make_jaxpr(lambda x, layer: llama.ffn_half(
        x, layer, cfg, training=training))(x, layer))
        for training in (True, False))
    assert trained == (3, 0, 1) and served == (3, 0, 0)


def test_the_dense_dispatch_refuses_a_share():
    cfg = moe.MoEConfig(64, 32, n_experts=8, held=(0, 4))
    with pytest.raises(NotImplementedError, match="share of the experts"):
        moe.moe_ffn(jnp.zeros((1, 4, 64)), {}, cfg)


# -------------------------------------------------------------- refusals
def test_config_refusals():
    with pytest.raises(ValueError, match="layer_pattern must be empty"):
        _cfg(layer_pattern=("attention", "window"), window_size=8,
             first_dense_layers=0, n_layers=4)
    with pytest.raises(ValueError, match="layer_pattern must be empty"):
        _cfg(layer_pattern=("mamba", "attention"), ssm_heads=4,
             first_dense_layers=0, n_layers=4)
    with pytest.raises(ValueError, match="query compression"):
        _cfg(q_lora_rank=0)
    with pytest.raises(ValueError, match="head_dim=32"):
        _cfg(head_dim=32)
    with pytest.raises(ValueError, match="only 'yarn'"):
        _cfg(rope_scaling={"type": "linear", "factor": 2})
    with pytest.raises(ValueError, match="must leave a layer"):
        _cfg(first_dense_layers=3)
    with pytest.raises(ValueError, match="prologue before a stack of expert"):
        _cfg(moe_experts=0, moe_groups=0, moe_held=())
    with pytest.raises(ValueError, match="moe_groups=3"):
        _cfg(moe_groups=3)
    with pytest.raises(ValueError, match="moe_top_groups=5"):
        _cfg(moe_top_groups=5)
    with pytest.raises(ValueError, match="moe_held="):
        _cfg(moe_held=(12, 8))


def test_training_and_the_single_stack_cache_refuse_the_config(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="served only"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)
    with pytest.raises(NotImplementedError, match="latent attention"):
        llama.forward_with_cache(
            params, jnp.zeros((1, 1), jnp.int32),
            jnp.zeros((1, 1), jnp.int32), {}, cfg)
    # YaRN alone is served only, too; a held share and a leading dense
    # layer alone are trained since PR 57 (tests/test_trinity_train.py)
    with pytest.raises(NotImplementedError, match="served only"):
        llama.forward(None, jnp.zeros((1, 8), jnp.int32),
                      LlamaConfig.debug(rope_scaling=YARN))
    for kw in (dict(moe_experts=4, moe_held=(0, 2)),
               dict(moe_experts=4, first_dense_layers=1)):
        assert LlamaConfig.debug(**kw).plain_decoder


def test_parts_and_parameter_trees(model):
    cfg, params = model
    (dense, key0, at0), (rest, key1, at1) = cfg.parts()
    assert (key0, at0, key1, at1) == ("dense_layers", 0, "layers", 1)
    assert (dense.n_layers, dense.moe_experts, rest.n_layers) == (1, 0, 2)
    assert LlamaConfig.debug().parts()[0][1:] == ("layers", 0)
    assert params["dense_layers"]["w_gate"].shape == (1, 64, 128)
    assert params["layers"]["w_gate"].shape == (2, 8, 64, 32)   # held
    assert params["layers"]["router"].shape == (2, 64, 16)      # all
    assert params["layers"]["ws_down"].shape == (2, 64, 64)
    assert params["layers"]["wk_b"].shape == (2, 4, 16, 32)
    assert not {"wq", "wk", "wv"} & set(params["layers"])
    axes = llama.param_logical_axes(cfg)
    assert jax.tree.structure(jax.tree.map(
        lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple))) \
        == jax.tree.structure(jax.tree.map(lambda a: 0, params))
    assert cfg.latent_row == 128 and cfg.o_dim == 64 and cfg.rope_dim == 8
    assert _cfg(kv_lora_rank=512, qk_rope_head_dim=64, head_dim=80
                ).latent_row == 640


# -------------------------------------------------------------- the engine
_presets = family.presets({"latent_debug_f32": _cfg})
engine = family.engines("latent_debug_f32", max_len=MAX_LEN)


def test_llm_server_serves_the_model_through_generate(model, engine):
    """``LLMServer.generate`` on the dense plane, no option: admission,
    prefill waves of several rows, chunks, slots reused by later requests
    (8 requests on 4 slots) -- every reply within TOL of the reference."""
    cfg, params = model
    family.serves_through_generate(
        engine(params=params),
        ((5, 19), (16, 12), (23, 17), (1, 24), (30, 6), (8, 10), (9, 25),
         (17, 11)), functools.partial(_gap, cfg, params), TOL)


@pytest.mark.parametrize("plane,args", [
    case for case in family.PLANES if case[0] != "prefix sharing"])
def test_planes_built_on_kv_rows_refuse_the_config(plane, args):
    family.refuses_plane("latent_debug_f32", plane, args, "latent attention")


def test_spans_counters_and_the_latent_pool_for_this_model_and_only_for_it(
        engine):
    """``serve.chunk`` carries the latent bytes its rows hold and the
    expert rows held and elsewhere, ``serve.prefill_group`` the same
    rows; ``kv_stats()`` and ``ray_tpu_kv_pool_bytes`` the latent pool;
    ``ray_tpu_serve_moe_expert_rows_elsewhere_total`` counts; an engine of
    a plain decoder emits none of it."""
    assert tracing.enabled()
    pools = metrics.kv_cache_counters()
    counters = metrics.serve_engine_counters()

    def series(name):
        return {program: counters[name].snapshot().get(("llm", program), 0.0)
                for program in ("prefill", "decode")}

    before = {name: series(name) for name in
              ("moe_expert_rows", "moe_expert_rows_elsewhere")}
    timeline.clear()
    # servers of its own, this one and the two plain ones below: every
    # span on the timeline and every counted row is this test's
    server = engine(fresh=True)
    cfg = server.cfg
    family.generate(server, [{"prompt": list(range(1, 1 + n)),
                              "max_new_tokens": 9} for n in (5, 12, 20)])
    family.settle(server)
    stats = server.kv_stats()
    server.shutdown()
    row = 3 * 128 * 4                   # 3 layers x 128 values x float32
    assert llama_serve.cache_pools(cfg, 4, 64) == {
        "latent": (4 * 64 * row, "float32")}
    assert llama_serve.state_bytes_per_slot(cfg) == {}
    assert stats["kv_pools"] == {"latent": {
        "bytes": 4 * 64 * row, "dtype": "float32",
        "bytes_per_slot": 64 * row, "bytes_per_position": row}}
    assert pools["pool_bytes"].snapshot()[("llm.latent", "float32")] \
        == 4 * 64 * row
    spans = timeline.export_timeline()
    groups = family.span_args(spans, "serve.prefill_group")
    chunks = family.span_args(spans, "serve.chunk")
    assert groups and chunks
    per_token = 2 * 3                   # 2 expert layers x top-3
    for g in groups:
        assert g["expert_rows"] + g["expert_rows_elsewhere"] \
            == g["prompt_tokens"] * per_token
        assert g["experts_touched"] <= 2 * 8
    for c in chunks:
        assert c["latent_bytes"] == c["kv_positions_attended"] * row
        assert c["expert_rows"] + c["expert_rows_elsewhere"] \
            == c["active"] * c["k"] * per_token
        assert c["expert_rows_max"] <= c["expert_rows"]
    assert sum(c["expert_rows_elsewhere"] for c in chunks) > 0
    moved = {name: {p: series(name)[p] - before[name][p]
                    for p in ("prefill", "decode")}
             for name in before}
    assert moved["moe_expert_rows"]["decode"] == sum(
        c["expert_rows"] for c in chunks)
    assert moved["moe_expert_rows_elsewhere"]["decode"] == sum(
        c["expert_rows_elsewhere"] for c in chunks)
    assert moved["moe_expert_rows"]["prefill"] \
        + moved["moe_expert_rows_elsewhere"]["prefill"] \
        == (5 + 12 + 20 + 1) * per_token

    timeline.clear()
    elsewhere = series("moe_expert_rows_elsewhere")
    for preset in ("debug", "moe_debug"):
        plain = engine(model_preset=preset, fresh=True)
        family.generate(plain, [{"prompt": [1, 2, 3], "max_new_tokens": 5}])
        family.settle(plain)
        assert "kv_pools" not in plain.kv_stats()
        assert "latent" not in llama_serve.cache_pools(plain.cfg, 4, 64)
    spans = timeline.export_timeline()
    seen = family.span_args(spans, "serve.chunk") \
        + family.span_args(spans, "serve.prefill_group")
    assert seen
    for args in seen:
        assert not [k for k in args if "latent" in k or "elsewhere" in k]
    assert series("moe_expert_rows_elsewhere") == elsewhere


def test_the_new_scopes_are_known_to_the_scope_map():
    from ray_tpu.observability import device

    for scope in ("mla_absorb", "mla_expand", "mla_decode_attention",
                  "shared_expert"):
        assert scope in device.SCOPES
    assert device.scope_of(
        "jit(decode_k)/sample/while/body/layer_scan/while/body/"
        "closed_call/mla_absorb/bhd,hdc->bhc/dot_general") \
        == ("mla_absorb", "forward")
    assert device.scope_of(
        "jit(prefill)/layer_scan/while/body/closed_call/ffn/"
        "shared_expert/dot_general") == ("shared_expert", "forward")


@pytest.mark.parametrize("over,stands", [(25, False), (26, True)])
def test_near_tie_swaps_are_taken_out_up_to_the_count_allowed(over, stands):
    """100 positions may hold 8 + 17 swaps: those are set to zero and the
    rest stands; one more and the request is given back as it was read."""
    assert reference.swaps_allowed(100) == 25
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
    counts = reference.gap_counts(gap)
    assert (counts["positions"], counts["over_0.25"]) == (100, over)

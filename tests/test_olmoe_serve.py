"""OLMoE's mathematics on the normal path, against its plain reference
(``benchmarks/references/olmoe_decoder.py``: float32, every expert on
every token, masked by top-k membership) at toy widths on the CPU:
hidden 64, 4 heads x 16, 8 experts of width 32, top-2 and top-3, 2
layers, RMSNorm on q and k, the gates unnormalised.

TOLERANCE.  Program and reference both compute in float32 here, on the
same float32 weights, so they differ by the order of their sums alone:
logits of size ~1 agree to a few 1e-6.  ``TOL`` = 2e-4 leaves that two
orders of room and is still forty times under what the nearest lower
precision does: the same reference run in bfloat16 is off by ~1e-2
(asserted below, so the tolerance cannot be met by lower precision).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.references import olmoe_decoder
from ray_tpu.models import llama, moe
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import metrics, timeline, tracing

TOL = 2e-4
LAYERS, EXPERTS, VOCAB = 2, 8, 256


def _published(top_k):
    """The toy configuration in the published key names (what the
    reference reads)."""
    return {"vocab_size": VOCAB, "hidden_size": 64,
            "num_hidden_layers": LAYERS, "num_attention_heads": 4,
            "num_key_value_heads": 4, "head_dim": 16,
            "intermediate_size": 32, "num_experts": EXPERTS,
            "num_experts_per_tok": top_k, "norm_topk_prob": False,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
            "tie_word_embeddings": False, "clip_qkv": None}


def _program_fields(top_k):
    return dict(vocab_size=VOCAB, hidden_size=64, n_layers=LAYERS,
                n_heads=4, n_kv_heads=4, head_dim=16, intermediate_size=32,
                max_seq_len=128, rope_theta=10000.0, norm_eps=1e-5,
                tie_embeddings=False, remat=False, dtype=jnp.float32,
                moe_experts=EXPERTS, moe_top_k=top_k, moe_norm_topk=False,
                qk_norm=True)


@functools.lru_cache(maxsize=None)
def _model(top_k, seed=0, zero_router=False):
    """(config, weights), the SAME objects at every call: ``engine`` tells
    weights apart by identity."""
    cfg = LlamaConfig(**_program_fields(top_k))
    params = family.init_params(jax.random.key(seed), cfg)
    # norms away from 1, so that a norm left out or misplaced shows
    keys = iter(jax.random.split(jax.random.key(seed + 100), 8))
    for name in ("attn_norm", "mlp_norm", "q_norm", "k_norm"):
        params["layers"][name] = 1.0 + 0.3 * jax.random.normal(
            next(keys), params["layers"][name].shape)
    if zero_router:
        # every logit equal: every token's top-k are experts 0..k-1, so
        # those take every token and the other experts none
        params["layers"]["router"] = jnp.zeros_like(
            params["layers"]["router"])
    return cfg, params


def _tokens(seed, shape):
    return jax.random.randint(jax.random.key(seed), shape, 0, VOCAB,
                              dtype=jnp.int32)


def _reference_logits(params, tokens, top_k, dtype=None, monkeypatch=None):
    """The reference's logits; with ``dtype``, the same reference run in
    that type (weights and activations), un-jitted so that the module's
    ``F32`` is read anew."""
    if dtype is None:
        return np.asarray(olmoe_decoder.logits(params, tokens,
                                               _published(top_k)))
    with monkeypatch.context() as m:
        m.setattr(olmoe_decoder, "F32", dtype)
        for jitted, plain in (("_layer_jit", "_layer"),
                              ("_embed_jit", "_embed"),
                              ("_head_jit", "_head")):
            m.setattr(olmoe_decoder, jitted, getattr(olmoe_decoder, plain))
        low = jax.tree.map(lambda x: x.astype(dtype), params)
        return np.asarray(olmoe_decoder.logits(
            low, tokens, _published(top_k)).astype(jnp.float32))


def _preset(top_k):
    return lambda **over: LlamaConfig(**{**_program_fields(top_k), **over})


# the presets by name, as the benchmark names its own
_presets = family.presets({f"olmoe_toy_k{top_k}": _preset(top_k)
                           for top_k in (2, 3)})
engine = family.engines(max_len=128)


def _engine(engine, top_k, **kw):
    """The file's server of the ``top_k`` toy: one a ``top_k`` for the
    tests that ask for no more."""
    return engine(model_preset=f"olmoe_toy_k{top_k}",
                  params=_model(top_k)[1], **kw)


# ---------------------------------------------------------------- forward
@pytest.mark.parametrize("top_k", [2, 3])
def test_forward_logits_match_the_reference(top_k, monkeypatch):
    cfg, params = _model(top_k)
    tokens = _tokens(1, (3, 24))
    want = _reference_logits(params, tokens, top_k)
    got = np.asarray(llama.forward(params, tokens, cfg))
    assert np.abs(got - want).max() <= TOL
    # ... and the nearest lower precision does not pass for it
    low = _reference_logits(params, tokens, top_k, jnp.bfloat16,
                            monkeypatch)
    assert np.abs(low - want).max() > 10 * TOL


@pytest.mark.parametrize("top_k", [2, 3])
def test_cache_paths_match_the_reference(top_k):
    """``prefill_forward`` (last real position of right-padded rows) and
    ``forward_with_cache`` (which refused experts before) against the
    reference's full forward pass."""
    cfg, params = _model(top_k)
    tokens = _tokens(2, (2, 16))
    want = _reference_logits(params, tokens, top_k)
    lengths = jnp.array([16, 9], jnp.int32)
    last, _ks, _vs = llama.prefill_forward(params, tokens, lengths, cfg)
    assert np.abs(np.asarray(last[0]) - want[0, 15]).max() <= TOL
    assert np.abs(np.asarray(last[1]) - want[1, 8]).max() <= TOL
    positions = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32), (2, 16))
    got, _cache = llama.forward_with_cache(
        params, tokens, positions, llama.init_kv_cache(cfg, 2, 32), cfg)
    assert np.abs(np.asarray(got) - want).max() <= TOL


# ------------------------------------------------- through a real LLMServer
@pytest.mark.parametrize("top_k", [2, 3])
def test_llm_server_prefill_then_decode_against_the_full_forward_pass(
        top_k, engine):
    """Requests through ``generate``: prefill, then ``_decode_k`` chunks
    through the cache, among other requests' rows.  The reference runs one
    full forward pass over prompt + emitted tokens; at every emitted
    position its logit of the emitted token must lie within TOL of its
    top logit (logits, not token equality: a near-tie may flip)."""
    cfg, params = _model(top_k)
    server = _engine(engine, top_k)
    rng = np.random.default_rng(top_k)
    requests = [{"prompt": rng.integers(0, VOCAB, n).tolist(),
                 "max_new_tokens": m}
                for n, m in ((5, 9), (16, 12), (23, 7), (11, 14), (30, 6),
                             (8, 10))]
    replies = family.generate(server, requests)
    for request, reply in zip(requests, replies):
        assert len(reply["tokens"]) == request["max_new_tokens"]
        gap = olmoe_decoder.teacher_forced_gap(
            params, request["prompt"], reply["tokens"], _published(top_k),
            pad_to=64)
        assert gap.shape == (request["max_new_tokens"],)
        assert gap.max() <= TOL, (request, gap)


def test_paged_plane_and_draft_inherit_the_expert_step(engine):
    """``decode_paged`` and the speculative draft reuse
    ``_make_decode_step``: the paged plane's tokens equal the dense
    plane's, bit for bit, with and without speculation."""
    requests = [{"prompt": list(range(3, 3 + n)), "max_new_tokens": m}
                for n, m in ((7, 10), (18, 6), (12, 13))]
    dense = family.generate(_engine(engine, 2), requests)
    paged = family.generate(_engine(engine, 2, paged=True, block_size=16),
                            requests)
    spec = family.generate(
        _engine(engine, 2, paged=True, block_size=16, spec_k=3,
                draft_layers=1), requests)
    for d, p, s in zip(dense, paged, spec):
        assert d["tokens"] == p["tokens"] == s["tokens"]


# ------------------------------------------------------------ no token drops
def test_every_token_reaches_its_experts_where_a_capacity_would_drop():
    """A router that sends EVERY token to experts 0 and 1: the dropless
    path agrees with the per-token reference; the dense dispatch with a
    capacity, on the same input, drops (whole rows come out zero)."""
    mcfg = moe.MoEConfig(hidden_size=32, intermediate_size=64,
                         n_experts=EXPERTS, top_k=2, norm_topk=False,
                         dtype=jnp.float32)
    params = moe.init_moe_params(jax.random.key(0), mcfg)
    params["router"] = jnp.zeros_like(params["router"])
    x = jax.random.normal(jax.random.key(1), (2, 16, 32))
    want = np.asarray(moe.moe_ffn_reference(x, params, mcfg))
    got, _aux, rows = moe.moe_ffn_dropless(x, params, mcfg)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                               atol=1e-6)
    assert rows.tolist() == [32, 32, 0, 0, 0, 0, 0, 0]
    dropped, _aux = moe.moe_ffn(x, params, mcfg)
    dropped = np.asarray(dropped).reshape(32, 32)
    # capacity = 32 * 2 / 8 * 1.25 = 10 rows an expert: 22 tokens lost
    assert (np.abs(dropped).max(-1) == 0).sum() == 22
    assert np.abs(want.reshape(32, 32)).max(-1).min() > 0


# ------------------------------------------ one un-sort and sum, every top-k
def _layer_of_experts(top_k, dtype, held=()):
    """(config, params, x (2, 12, 32)): 16 experts of 32 x 64, all of them
    or the share ``held``; 24 tokens, so ``T x K`` is 96 / 144 / 192 rows
    (top-6 and top-4 are what a chip pads to a sublane tile as (T, K, D))."""
    mcfg = moe.MoEConfig(hidden_size=32, intermediate_size=64, n_experts=16,
                         top_k=top_k, norm_topk=True, dtype=dtype, held=held)
    params = moe.init_moe_params(jax.random.key(top_k), mcfg)
    if held:
        first, count = held
        params = {k: v if k == "router" else v[first:first + count]
                  for k, v in params.items()}
    return mcfg, params, jax.random.normal(jax.random.key(1), (2, 12, 32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("how", ["every_row", "valid", "layer_index"])
@pytest.mark.parametrize("top_k", [4, 6, 8])
def test_whole_expert_layer_agrees_with_the_reference(top_k, how, dtype):
    """``moe_ffn_dropless`` of a configuration that holds all its experts
    against the per-token reference in the same type: with every row real,
    with a ``valid`` mask (the rows that are not real come out zero and
    take no expert's row) and as layer 1 of a stack of three."""
    mcfg, params, x = _layer_of_experts(top_k, dtype)
    x = x.astype(dtype)
    want = np.asarray(moe.moe_ffn_reference(x, params, mcfg), np.float32)
    real = 24
    if how == "every_row":
        got, _aux, rows = jax.jit(
            lambda x: moe.moe_ffn_dropless(x, params, mcfg))(x)
    elif how == "valid":
        valid = jax.random.bernoulli(jax.random.key(2), 0.6, (2, 12))
        real = int(valid.sum())
        assert 0 < real < 24
        want = np.where(np.asarray(valid)[..., None], want, 0.0)
        got, _aux, rows = jax.jit(
            lambda x, valid: moe.moe_ffn_dropless(x, params, mcfg, valid))(
                x, valid)
    else:
        below, above = (moe.init_moe_params(jax.random.key(50 + i), mcfg)
                        for i in range(2))
        stack = {k: v if k == "router" else jnp.stack([below[k], v, above[k]])
                 for k, v in params.items()}
        got, _aux, rows = jax.jit(
            lambda x, layer: moe.moe_ffn_dropless(
                x, stack, mcfg, layer_index=layer))(x, jnp.int32(1))
    assert got.dtype == dtype and got.shape == x.shape
    assert rows.shape == (16,) and int(rows.sum()) == real * top_k
    got = np.asarray(got, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    else:
        # a result row is rounded to bfloat16 once in either form (on its
        # way out of the matmul, of the un-sort); the sum under the gates is
        # float32 in both
        assert np.abs(got - want).max() <= 2 ** -7 * np.abs(want).max()


def _every_equation(jaxpr):
    """The equations of ``jaxpr``, those inside its equations too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _every_equation(inner)


@pytest.mark.parametrize("held", [(), (4, 8)], ids=["whole", "held"])
@pytest.mark.parametrize("top_k", [4, 6, 8])
def test_a_serving_expert_layer_keeps_no_unsorted_copy_of_its_rows(
        top_k, held):
    """A result row of the grouped matmuls is written once and gathered
    once, as its token's k-th result and in the stream's type, into the
    float32 sum, held or whole: the traced program has no un-sorted array
    of all ``T x K`` rows -- not ``(T, K, D)`` (on the chip ``K`` = 4 or 6
    there is padded to 8), not ``(T, K x D)`` (every row in another tile
    than it was written in), not ``(T x K, D)`` a second time -- and what
    leaves a gather in float32 is cast before anything else reads it.
    With and without a ``valid`` mask."""
    mcfg, params, x = _layer_of_experts(top_k, jnp.bfloat16, held)
    T, D = 24, 32
    for valid in (None, jnp.ones((2, 12), bool)):
        eqns = list(_every_equation(jax.make_jaxpr(
            lambda x: moe.moe_ffn_dropless(x, params, mcfg, valid))(x).jaxpr))
        made = [(eqn.primitive.name, tuple(eqn.outvars[0].aval.shape),
                 eqn.outvars[0].aval.dtype.name) for eqn in eqns]
        shapes = {shape for _op, shape, _dtype in made}
        assert not {(T, top_k, D), (T, top_k * D), (top_k, T, D)} & shapes
        # all the rows at once: gathered in, and out of the third matmul
        # (float32 where the benchmark's cell test holds that: a whole
        # configuration's) -- nothing else
        assert [(op, dtype) for op, shape, dtype in made
                if shape == (T * top_k, D)] == [
            ("gather", "bfloat16"),
            ("ragged_dot_general", "bfloat16" if held else "float32")]
        assert sum(op == "gather" and shape == (T, D)
                   for op, shape, _dtype in made) == top_k
        for i, eqn in enumerate(eqns):
            if made[i] == ("gather", (T, D), "float32"):
                readers = [e for e in eqns if eqn.outvars[0] in e.invars]
                assert [e.primitive.name for e in readers] == [
                    "convert_element_type"]
                assert readers[0].outvars[0].aval.dtype.name == "bfloat16"
        assert ("add", (T, D), "float32") in made   # the sum under the gates


@pytest.mark.parametrize("top_k", [2, 3])
def test_skewed_router_agrees_with_the_reference(top_k):
    cfg, params = _model(top_k, zero_router=True)
    tokens = _tokens(4, (2, 20))
    want = _reference_logits(params, tokens, top_k)
    got = np.asarray(llama.forward(params, tokens, cfg))
    assert np.abs(got - want).max() <= TOL
    _last, _ks, _vs, rows = llama.prefill_forward(
        params, tokens, jnp.array([20, 20], jnp.int32), cfg,
        return_expert_rows=True)
    assert np.asarray(rows).tolist() == [
        [40] * top_k + [0] * (EXPERTS - top_k)] * LAYERS


def test_gate_convention_is_the_routing_functions_argument():
    """One routing function; renormalised gates sum to one, the others
    are the softmax's own probabilities."""
    x = jax.random.normal(jax.random.key(0), (12, 32))
    router = jax.random.normal(jax.random.key(1), (32, EXPERTS))
    probs, raw, idx = moe._route(x, router, 3, False)
    _p, normed, idx2 = moe._route(x, router, 3, True)
    assert np.array_equal(idx, idx2)
    np.testing.assert_allclose(normed.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_allclose(
        raw, np.take_along_axis(np.asarray(probs), np.asarray(idx), -1))
    assert float(raw.sum(-1).max()) < 1.0


# ------------------------------------- padding and inactive rows cost nothing
@pytest.mark.parametrize("top_k", [2, 3])
def test_prefill_padding_changes_nothing_and_takes_no_expert_rows(top_k):
    cfg, params = _model(top_k)
    tokens = np.asarray(_tokens(5, (4, 16)))
    lengths = np.array([16, 5, 11, 0], np.int32)   # row 3: group padding
    other = tokens.copy()
    for row, n in enumerate(lengths):
        other[row, n:] = (other[row, n:] + 17) % VOCAB   # padding differs
    a = llama.prefill_forward(params, jnp.asarray(tokens),
                              jnp.asarray(lengths), cfg,
                              return_expert_rows=True)
    b = llama.prefill_forward(params, jnp.asarray(other),
                              jnp.asarray(lengths), cfg,
                              return_expert_rows=True)
    np.testing.assert_array_equal(np.asarray(a[0])[:3], np.asarray(b[0])[:3])
    for ka, kb in zip((a[1], a[2]), (b[1], b[2])):      # real K/V rows
        for row, n in enumerate(lengths):
            np.testing.assert_array_equal(np.asarray(ka)[:, row, :n],
                                          np.asarray(kb)[:, row, :n])
    rows = np.asarray(a[3])
    assert rows.shape == (LAYERS, EXPERTS)
    assert rows.sum(1).tolist() == [int(lengths.sum()) * top_k] * LAYERS
    np.testing.assert_array_equal(rows, np.asarray(b[3]))


@pytest.mark.parametrize("top_k", [2, 3])
def test_inactive_slots_change_nothing_and_take_no_expert_rows(top_k,
                                                               engine):
    cfg, params = _model(top_k)
    server = _engine(engine, top_k)
    shape = llama.init_kv_cache(cfg, 4, 128)["k"].shape
    kk, kv = jax.random.split(jax.random.key(9))
    cache = {"k": jax.random.normal(kk, shape), "v": jax.random.normal(
        kv, shape)}
    active = jnp.array([True, False, True, False])
    lens = jnp.array([7, 3, 12, 0], jnp.int32)
    zeros = jnp.zeros(4, jnp.int32)

    def run(tok):
        return server._decode_k(
            server.params, jax.tree.map(jnp.copy, cache),
            jnp.asarray(tok, jnp.int32), jnp.copy(lens), zeros, zeros,
            jnp.zeros(4, bool), active, k=4, s_active=64)

    a, b = run([3, 5, 7, 9]), run([3, 200, 7, 31])   # inactive rows differ
    np.testing.assert_array_equal(np.asarray(a[1])[:, [0, 2]],
                                  np.asarray(b[1])[:, [0, 2]])
    for got in (a, b):
        rows, touched = got[4]
        assert rows.shape == (LAYERS, EXPERTS)
        # 2 active slots x 4 steps x top_k, in every layer; no more
        assert np.asarray(rows).sum(1).tolist() == [2 * 4 * top_k] * LAYERS
        assert 0 < int(touched) <= 4 * LAYERS * min(EXPERTS, 2 * top_k)


def test_spans_and_counters_say_what_the_experts_computed(engine):
    """``serve.prefill_group`` / ``serve.chunk`` carry the expert load and
    the ``ray_tpu_serve_moe_*`` series count it: prompt tokens x top-k x
    layers for prefill (padding adds nothing), active slots x steps x
    top-k x layers for decode; a dense engine emits none of it."""
    assert tracing.enabled()
    top_k = 2
    cfg, params = _model(top_k)
    tags = {"deployment": "llm"}
    group = metrics.serve_engine_counters()

    def series():
        return {(name, program): group[name].snapshot().get(
            ("llm", program), 0.0)
            for name in ("moe_expert_rows", "moe_experts_touched")
            for program in ("prefill", "decode")}

    timeline.clear()
    before = series()
    # a server of its own, and the dense one below: every span on the
    # timeline is counted, and the last chunk's is written by the time the
    # scheduler's thread has been joined (``shutdown``)
    server = _engine(engine, top_k, fresh=True)
    requests = [{"prompt": list(range(1, 1 + n)), "max_new_tokens": 6}
                for n in (5, 9, 20)]
    family.generate(server, requests)
    server.shutdown()
    spans = timeline.export_timeline()
    groups = family.span_args(spans, "serve.prefill_group")
    chunks = family.span_args(spans, "serve.chunk")
    assert groups and chunks
    per_token = top_k * LAYERS
    for g in groups:
        assert g["expert_rows"] == g["prompt_tokens"] * per_token
        assert g["expert_rows"] < g["token_positions"] * per_token
        assert 0 < g["expert_rows_max"] <= g["prompt_tokens"]
        assert 0 < g["experts_touched"] <= LAYERS * EXPERTS
    for c in chunks:
        assert c["expert_rows"] == c["active"] * c["k"] * per_token
        assert c["expert_rows"] <= c["token_steps"] * per_token
        assert 0 < c["experts_touched"] <= c["k"] * LAYERS * EXPERTS
    after = series()
    moved = {key: after[key] - before[key] for key in after}
    assert moved[("moe_expert_rows", "prefill")] == (5 + 9 + 20) * per_token
    assert moved[("moe_expert_rows", "decode")] == sum(
        c["expert_rows"] for c in chunks)
    assert moved[("moe_experts_touched", "decode")] == sum(
        c["experts_touched"] for c in chunks)
    assert group["moe_load_imbalance"].buckets(
        {**tags, "program": "decode"})

    # a dense engine: none of the attributes, none of the series
    timeline.clear()
    dense = engine(model_preset="debug", fresh=True)
    family.generate(dense, requests[:1])
    dense.shutdown()
    for e in timeline.export_timeline():
        if e.get("name") in ("serve.chunk", "serve.prefill_group"):
            assert not {"expert_rows", "expert_rows_max",
                        "experts_touched"} & set(e["args"])
    assert series() == after

"""The seam between the scheduler and the device programs.

``models/llama_serve.py`` builds every program the chip runs when a
llama-family config is served, from the config alone; ``serve/llm.py`` is
their scheduler and knows nothing of what a decoder layer is made of.
Held here: the programs lower with abstract arguments and no engine,
thread or weights; the one built from a config IS the engine's; every
user of ``llama.layer_walk`` (and the decode step, which is the walk
written out) computes what ``llama.forward`` computes; and the arrow
between the two modules points one way.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from ray_tpu.models import llama, llama_serve
from ray_tpu.models.llama import LlamaConfig

# float32 program against float32 ``forward`` on the same weights: they
# differ by the order of their sums alone (tests/test_olmoe_serve.py)
TOL = 2e-4
PRESETS = {"dense": "debug", "experts": "moe_debug"}
SLOTS, MAX_LEN, BLOCK, CHUNK = 4, 64, 16, 8


def _cfg(kind, **kw):
    return getattr(LlamaConfig, PRESETS[kind])(max_seq_len=MAX_LEN, **kw)


def _arr(dtype, *shape):
    return jax.ShapeDtypeStruct(shape, dtype)


def _shapes(tree):
    return jax.tree.map(lambda x: (x.shape, jnp.dtype(x.dtype).name), tree)


# ------------------------------------- from a config alone, nothing running
def _abstract_params(cfg):
    return jax.eval_shape(
        lambda k: llama.init_params(k, cfg, cfg.dtype), jax.random.key(0))


def _lowered_dense(cfg):
    params = _abstract_params(cfg)
    cache = jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, SLOTS, MAX_LEN))
    i32, flag = jnp.int32, jnp.bool_
    yield "prefill", cache, llama_serve.build_prefill(cfg).lower(
        params, cache, _arr(i32, 4, 16), _arr(i32, 4), _arr(i32, 4))
    yield "decode_k", cache, llama_serve.build_decode_k(cfg).lower(
        params, cache, *[_arr(i32, SLOTS)] * 4, _arr(flag, SLOTS),
        _arr(flag, SLOTS), k=CHUNK, s_active=MAX_LEN)
    yield "draft_prefill", cache, llama_serve.build_draft_prefill(
        cfg).lower(params, cache, _arr(i32, 4, 16), _arr(i32, 4),
                   _arr(i32, 4))
    yield "draft_propose", cache, llama_serve.build_draft_propose(
        cfg).lower(params, cache, _arr(i32, SLOTS), _arr(i32, SLOTS),
                   _arr(flag, SLOTS), k=3, s_active=MAX_LEN)


def _lowered_paged(cfg, kv_quant):
    blocks = llama_serve.BlockPool(cfg, BLOCK, kv_quant)
    params = _abstract_params(cfg)
    pool = jax.eval_shape(lambda: llama.init_paged_kv_cache(
        cfg, 17, BLOCK, kv_quant=kv_quant))
    i32, flag = jnp.int32, jnp.bool_
    handed = _arr(cfg.dtype, 2, cfg.n_layers, BLOCK, cfg.n_kv_heads,
                  cfg.head_dim)
    yield "prefill_cold", pool, llama_serve.build_prefill_cold(
        blocks).lower(params, pool, _arr(i32, 4, 32), _arr(i32, 4),
                      _arr(i32, 4, 2))
    yield "prefill_warm", pool, llama_serve.build_prefill_warm(
        blocks).lower(params, pool, _arr(i32, 4, 32), _arr(i32, 4),
                      _arr(i32, 4), _arr(i32, 4, 1), _arr(i32, 4, 2))
    yield "decode_paged", pool, llama_serve.build_decode_paged(
        blocks).lower(params, pool, *[_arr(i32, SLOTS)] * 4,
                      _arr(flag, SLOTS), _arr(flag, SLOTS),
                      _arr(i32, SLOTS, 4), k=CHUNK)
    yield "inject", pool, llama_serve.build_inject(blocks).lower(
        pool, handed, handed, _arr(i32, 2))
    yield "spec_verify", pool, llama_serve.build_spec_verify(
        blocks).lower(params, pool, _arr(i32, SLOTS, 3),
                      _arr(i32, SLOTS, 3), _arr(flag, SLOTS),
                      _arr(i32, SLOTS, 4))


@pytest.mark.parametrize("kind", list(PRESETS))
@pytest.mark.parametrize("plane", ["dense", "paged", "paged_int8"])
def test_programs_lower_from_a_config_alone(kind, plane):
    """No ``LLMServer``, no thread, no weights: a config (and for the
    paged plane a block size and format) is all a program needs.  Each
    hands its donated cache back in the shape it came in, and is named
    what the device trace and the benchmark's readers find it by."""
    cfg = _cfg(kind)
    lowered = (_lowered_dense(cfg) if plane == "dense" else
               _lowered_paged(cfg, "int8" if plane == "paged_int8"
                              else None))
    names = []
    for name, cache, low in lowered:
        names.append(name)
        assert f"@jit_{name}" in low.as_text()
        out = low.out_info
        kept = out if name in ("inject", "draft_prefill") else out[0]
        assert _shapes(kept) == _shapes(cache), name
        if name in ("decode_k", "decode_paged"):
            assert out[1].shape == (CHUNK, SLOTS)
            # a model with experts hands back their load, a dense one ()
            assert (out[4] == ()) == (cfg.moe_experts == 0)
    assert len(names) == (4 if plane == "dense" else 5)


def _primitives(jaxpr):
    """Names of every primitive in a jaxpr, those of its sub-jaxprs
    (a scan's body, a jitted call) among them."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitives(sub)
    return names


@pytest.mark.parametrize("kind", list(PRESETS))
def test_a_serve_program_holds_no_checkpoint(kind):
    """Training hands the walk its remat as a wrapper of the block;
    serving hands it none, whatever ``remat`` says (True by default): the
    prefill and the decode step differentiate nothing, and neither their
    jaxprs nor their lowered text hold a ``checkpoint`` / ``remat``
    call."""
    cfg = _cfg(kind, remat=True)
    args = {name: low for name, _cache, low in _lowered_dense(cfg)}
    assert {"prefill", "decode_k"} <= set(args)
    for name in ("prefill", "decode_k"):
        text = args[name].as_text(debug_info=True)
        assert not re.search(r"\bcheckpoint\b|remat", text), name
    params, cache = _abstract_params(cfg), jax.eval_shape(
        lambda: llama.init_kv_cache(cfg, SLOTS, MAX_LEN))
    traced = jax.make_jaxpr(llama_serve.build_prefill(cfg))(
        params, cache, _arr(jnp.int32, 4, 16), _arr(jnp.int32, 4),
        _arr(jnp.int32, 4))
    remat = {"checkpoint", "remat", "remat2"}    # the primitive's names
    names = _primitives(traced.jaxpr)
    assert "scan" in names and not names & remat
    # and training's own walk, under the same config, does hold one
    loss = jax.make_jaxpr(lambda p, t: llama.forward(p, t, cfg))(
        params, _arr(jnp.int32, 2, 16))
    assert _primitives(loss.jaxpr) & remat


# -------------------------------------------- a layer is written once
def test_a_layer_is_made_of_its_pieces_in_one_place():
    """``attn_out_ffn``, ``ffn_half`` and ``state_mixer`` -- what follows
    a layer's mixer, and which module a state-keeping kind's mixer is --
    are called from ``llama.layer_block`` and from nowhere else in the
    three modules that walk layers (``attn_out_ffn`` is itself the output
    projection before ``ffn_half``); no ``decoder_layer`` is left."""
    import inspect

    from ray_tpu.models import llama_pipeline

    called = re.compile(r"(?<!def )(?<![\w`.])(?:llama\.)?"
                        r"(attn_out_ffn|ffn_half|state_mixer)\(")
    once = (llama.layer_block, llama.attn_out_ffn)
    assert set(called.findall(inspect.getsource(once[0]))) == {
        "attn_out_ffn", "ffn_half", "state_mixer"}
    assert called.findall(inspect.getsource(once[1])) == ["ffn_half"]
    for module in (llama, llama_serve, llama_pipeline):
        source = inspect.getsource(module)
        assert "def decoder_layer" not in source
        if module is llama:
            for fn in once:
                source = source.replace(inspect.getsource(fn), "")
        assert called.findall(source) == [], module.__name__


# ------------------------------------------- the engine's programs ARE these
@pytest.mark.parametrize("kind", list(PRESETS))
def test_decode_k_from_a_config_is_the_engines(kind):
    """``build_decode_k(cfg)`` and a live engine's ``_decode_k`` on the
    same cache and inputs: the same tokens, carries and cache, bit for
    bit."""
    from ray_tpu.serve import llm

    server = llm.LLMServer(model_preset=PRESETS[kind], max_slots=SLOTS,
                           max_len=MAX_LEN, prefill_buckets=(16,),
                           warmup=False)
    try:
        cfg, params = server.cfg, server.params
        shape = server.cache["k"].shape
        kk, kv = jax.random.split(jax.random.key(3))

        def inputs():           # fresh each time: the programs donate
            cache = {"k": jax.random.normal(kk, shape, cfg.dtype),
                     "v": jax.random.normal(kv, shape, cfg.dtype)}
            return (params, cache, jnp.array([7, 1, 200, 31], jnp.int32),
                    jnp.array([5, 17, 40, 1], jnp.int32),
                    jnp.array([0, 9, 0, 0], jnp.int32),
                    jnp.array([0, 30, 0, 0], jnp.int32),
                    jnp.array([False, True, False, False]),
                    jnp.array([True, True, False, True]))

        ours = llama_serve.build_decode_k(cfg)(
            *inputs(), k=CHUNK, s_active=MAX_LEN)
        theirs = server._decode_k(*inputs(), k=CHUNK, s_active=MAX_LEN)
    finally:
        server.shutdown()
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(
            np.asarray(a.astype(jnp.float32)),
            np.asarray(b.astype(jnp.float32)))


# ------------------------------- one walk: every user computes ``forward``
T = 12


@pytest.fixture(scope="module", params=list(PRESETS))
def model(request):
    """float32 toy weights, the tokens, and what ``forward`` says."""
    cfg = _cfg(request.param, dtype=jnp.float32, tie_embeddings=False)
    params = family.init_params(jax.random.key(1), cfg)
    tokens = jax.random.randint(jax.random.key(2), (SLOTS, T), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    want = np.asarray(llama.forward(params, tokens, cfg))
    _last, ks, vs = llama.prefill_forward(
        params, tokens, jnp.full(SLOTS, T, jnp.int32), cfg)
    return cfg, params, tokens, want, np.asarray(ks), np.asarray(vs)


def _greedy_under(want, toks):
    """Every emitted token's logit lies within TOL of the top one
    (logits, not token equality: a near-tie may flip)."""
    want, toks = np.asarray(want), np.asarray(toks)
    picked = np.take_along_axis(want, toks[..., None], axis=-1)[..., 0]
    return (want.max(-1) - picked).max() <= TOL


def test_prefill_forward_reads_the_last_real_position(model):
    cfg, params, tokens, want, _ks, _vs = model
    lengths = jnp.array([T, 9, 1, 5], jnp.int32)
    last, _ks, _vs, rows = llama.prefill_forward(
        params, tokens, lengths, cfg, return_expert_rows=True)
    for row, n in enumerate(np.asarray(lengths)):
        assert np.abs(np.asarray(last[row]) - want[row, n - 1]).max() <= TOL
    if cfg.moe_experts:     # experts computed the real positions alone
        assert np.asarray(rows).sum(1).tolist() == \
            [int(lengths.sum()) * cfg.moe_top_k] * cfg.n_layers
    else:
        assert rows is None


def test_forward_with_cache_holds_t_positions_at_once(model):
    cfg, params, tokens, want, ks, vs = model
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                 (SLOTS, T))
    got, cache = llama.forward_with_cache(
        params, tokens, positions,
        llama.init_kv_cache(cfg, SLOTS, MAX_LEN), cfg)
    assert np.abs(np.asarray(got) - want).max() <= TOL
    assert np.abs(np.asarray(cache["k"])[:, :, :T] - ks).max() <= TOL
    assert np.abs(np.asarray(cache["v"])[:, :, :T] - vs).max() <= TOL


def test_decode_step_one_token_at_a_time(model):
    """The decode step is the walk written out (its K/V are the layer
    scan's carry): teacher-forced through an empty cache it emits
    ``forward``'s greedy tokens and leaves ``prefill_forward``'s rows."""
    cfg, params, tokens, want, ks, vs = model
    active = jnp.ones(SLOTS, bool)

    @jax.jit
    def step(ck, cv, tok, lens):
        carry, (nxt, _rows) = llama_serve.decode_step(
            cfg, params, 32, active)((ck, cv, tok, lens), None)
        return carry[0], carry[1], nxt

    cache = llama.init_kv_cache(cfg, SLOTS, MAX_LEN)
    ck, cv, emitted = cache["k"], cache["v"], []
    for t in range(T):
        ck, cv, nxt = step(ck, cv, tokens[:, t],
                           jnp.full(SLOTS, t, jnp.int32))
        emitted.append(np.asarray(nxt))
    assert _greedy_under(want, np.stack(emitted, axis=1))
    assert np.abs(np.asarray(ck)[:, :, :T] - ks).max() <= TOL
    assert np.abs(np.asarray(cv)[:, :, :T] - vs).max() <= TOL
    assert not np.asarray(ck)[:, :, T:].any()      # and nothing beyond


def test_spec_verify_t_tokens_at_once(model):
    """``spec_verify`` over a block pool: T tokens a slot in one pass
    emit ``forward``'s greedy tokens and write ``prefill_forward``'s rows
    into the slots' blocks; an inactive slot writes nothing."""
    cfg, params, tokens, want, ks, _vs = model
    blocks = llama_serve.BlockPool(cfg, BLOCK, None)
    pool = llama.init_paged_kv_cache(cfg, 1 + 2 * SLOTS, BLOCK)
    bt = 1 + jnp.arange(2 * SLOTS, dtype=jnp.int32).reshape(SLOTS, 2)
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32),
                                 (SLOTS, T))
    active = jnp.array([True, True, False, True])
    pool, toks = llama_serve.build_spec_verify(blocks)(
        params, pool, tokens, positions, active, bt)
    on = np.asarray(active)
    assert _greedy_under(want[on], np.asarray(toks)[on])
    written = np.asarray(blocks.gather(pool, "k", bt))    # (L, B, 32, ..)
    assert np.abs(written[:, on, :T] - ks[:, on]).max() <= TOL
    assert not written[:, ~on].any() and not written[:, :, T:].any()


# --------------------------------------------- blocks handed off and back
@pytest.mark.parametrize("kv_quant,tol", [(None, 0.0), ("int8", 0.02)])
def test_extract_is_injects_inverse(kv_quant, tol):
    """What the disaggregated hand-off rests on: blocks go in through
    ``inject`` and come back out of ``BlockPool.extract`` at full
    precision, whatever the pool stores (int8: one part in 127 of a
    row's largest value)."""
    cfg = _cfg("dense")
    blocks = llama_serve.BlockPool(cfg, BLOCK, kv_quant)
    pool = llama.init_paged_kv_cache(cfg, 9, BLOCK, kv_quant=kv_quant)
    shape = (3, cfg.n_layers, BLOCK, cfg.n_kv_heads, cfg.head_dim)
    kb = jax.random.normal(jax.random.key(5), shape, cfg.dtype)
    vb = jax.random.normal(jax.random.key(6), shape, cfg.dtype)
    dest = jnp.array([4, 2, 7], jnp.int32)
    pool = llama_serve.build_inject(blocks)(pool, kb, vb, dest)
    got_k, got_v = blocks.extract(pool, dest)
    assert got_k.dtype == got_v.dtype == cfg.dtype
    for got, sent in ((got_k, kb), (got_v, vb)):
        err = np.abs(np.asarray(got, np.float32)
                     - np.asarray(sent, np.float32))
        assert err.max() <= tol * np.abs(np.asarray(sent, np.float32)).max()


# ------------------------------------------------ the arrow points one way
def test_the_scheduler_knows_no_layer():
    """``serve/llm.py`` jits no model program, calls no ``llama._*``
    private and names none of the pieces a decoder layer is made of:
    they are ``models/llama.py``'s and ``models/llama_serve.py``'s."""
    from ray_tpu.serve import llm

    with open(llm.__file__) as f:
        source = f.read()
    assert not re.search(r"\bllama\._\w+", source)
    assert not re.search(r"\bjax\.jit\b|\bpjit\b", source)
    for name in ("_qkv_rope", "attn_out_ffn", "split_expert_stacks",
                 "rope_table", "rms_norm", "_cache_attend",
                 "tie_embeddings", "quantize_kv_blocks"):
        assert name not in source, name
    with open(llama_serve.__file__) as f:
        assert "ray_tpu.serve.llm" not in f.read()

"""A hybrid of Mamba-2 and attention layers (granite-4.0-h-micro's
shape, toy widths, prefill chunks of 8) through the dense serving plane,
held to ``benchmarks/references/granite_hybrid_decoder.py``: float32, the
recurrence a sequential scan over positions, no cache, no chunks.

- the chunked prefill scan alone against the sequential recurrence, at
  lengths around a chunk and for a right-padded group of unequal lengths;
- prefill then decode through ``build_prefill`` / ``build_decode_k``
  against the reference's full forward pass (logits, not tokens);
- a slot that sits out a chunk keeps its state, a reused slot inherits
  nothing;
- four broken variants each FAIL the comparison: a wrong state row, a
  state advanced on an inactive slot, a dropped conv tap, a state rounded
  to int8;
- the planes that cannot hold a recurrent state refuse the config;
- spans, counters and pool sizes exist for a hybrid and only for one.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.references import granite_hybrid_decoder as reference
from ray_tpu.models import llama, llama_serve, mamba2
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.observability import metrics, timeline, tracing

VOCAB, SLOTS, MAX_LEN, CHUNK = 256, 4, 128, 8
# The toy model computes in float32, as the reference does, so the two
# differ by the ORDER of float32 sums alone (chunked against sequential,
# cached against whole): a gap between the reference's top logit and its
# logit of an emitted token is a near-tie of ~1e-5 deviations.  A broken
# variant emits arbitrary tokens: gaps of whole deviations.
TOL = 1e-3
# What ``kinds/serve_llm.py`` allows a bfloat16 engine (LOGIT_MARGIN), in
# units of the logits' deviation: the broken variants must be over it too.
MARGIN = 0.25


def _cfg(**kw):
    base = dict(vocab_size=VOCAB, max_seq_len=MAX_LEN, dtype=jnp.float32)
    base.update(kw)
    return LlamaConfig.hybrid_debug(**base)


def _published(cfg):
    """The toy configuration in the published key names (what the
    reference reads)."""
    return {"layer_types": list(cfg.period) * (cfg.n_layers
                                               // len(cfg.period)),
            "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "attention_multiplier": cfg.attention_multiplier,
            "embedding_multiplier": cfg.embedding_multiplier,
            "residual_multiplier": cfg.residual_multiplier,
            "logits_scaling": cfg.logits_scaling,
            "rms_norm_eps": cfg.norm_eps, "mamba_n_heads": cfg.ssm_heads,
            "mamba_d_head": cfg.ssm_head_dim,
            "mamba_d_state": cfg.ssm_state, "mamba_n_groups": 1,
            "position_embedding_type": "nope",
            "tie_word_embeddings": True}


@pytest.fixture(scope="module")
def model():
    return _model(_cfg())


def _model(cfg):
    params = family.init_params(jax.random.key(0), cfg)
    # norms, the conv bias and D away from their initial constants, so
    # that one left out or misplaced shows
    keys = iter(jax.random.split(jax.random.key(100), 8))
    layers = params["layers"]
    for name in ("attn_norm", "mlp_norm", "ssm_norm"):
        layers[name] = 1.0 + 0.3 * jax.random.normal(next(keys),
                                                     layers[name].shape)
    layers["ssm_conv_b"] = 0.2 * jax.random.normal(
        next(keys), layers["ssm_conv_b"].shape)
    # ... and a recurrence that MATTERS.  Under the initial values (dt in
    # [1e-3, 1e-1], D = 1) the skip path D x outweighs S C by an order of
    # magnitude and a toy's tokens do not depend on its state at all
    # (rows swapped between slots: not one token of 36 changed).  dt near
    # 0.5, slow decays and a small D make every token read the state.
    layers["ssm_dt_bias"] = jnp.full_like(layers["ssm_dt_bias"],
                                          float(np.log(np.expm1(0.5))))
    layers["ssm_A_log"] = jnp.log(jax.random.uniform(
        next(keys), layers["ssm_A_log"].shape, minval=0.02, maxval=0.5))
    layers["ssm_D"] = jnp.full_like(layers["ssm_D"], 0.2)
    return cfg, params


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).tolist() for n in lengths]


def _padded(prompts, bucket):
    toks = np.zeros((len(prompts), bucket), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    return jnp.asarray(toks), jnp.asarray([len(p) for p in prompts],
                                          jnp.int32)


# ------------------------------------------------------ the scan, by itself
def _sequential(x, dt, A, B, C):
    """The recurrence as defined, one position at a time (numpy,
    float64), B and C (G, P, R, N) in R groups, head j reading group j //
    (nh / R): (y (G, P, nh, hd), the state after each row's LAST position
    with dt > 0)."""
    x, dt, A, B, C = (np.asarray(a, np.float64) for a in (x, dt, A, B, C))
    G, P, nh, hd = x.shape
    B, C = (np.repeat(a, nh // a.shape[2], axis=2) for a in (B, C))
    s = np.zeros((G, nh, hd, B.shape[-1]))
    ys = np.zeros((G, P, nh, hd))
    for t in range(P):
        s = (np.exp(dt[:, t] * A)[..., None, None] * s
             + (dt[:, t, :, None] * x[:, t])[..., None]
             * B[:, t, :, None, :])
        ys[:, t] = np.einsum("ghdn,ghn->ghd", s, C[:, t])
    return ys, s


@pytest.mark.parametrize("groups", [1, 2], ids=["one-group", "two-groups"])
@pytest.mark.parametrize("lengths", [(1,), (7,), (8,), (9,), (20,),
                                     (20, 3, 8, 17)],
                         ids=["1", "7", "8", "9", "20", "padded-group"])
def test_chunked_scan_is_the_sequential_recurrence(lengths, groups):
    """``ssd_chunked`` in chunks of 8 over right-padded rows (dt = 0 past
    a row's length) gives the recurrence's outputs at every real position
    and, as its final state, the state at EACH ROW'S OWN last real
    position: lengths inside a chunk, on its edge, one past it, over
    several, and unequal lengths in one group; with one group of B and C
    (Granite 4) and with two, each read by its half of the heads
    (Nemotron-H has eight)."""
    G, P, nh, hd, N = len(lengths), 24, 4, 8, 16
    keys = jax.random.split(jax.random.key(sum(lengths)), 5)
    x = jax.random.normal(keys[0], (G, P, nh, hd))
    B = jax.random.normal(keys[1], (G, P, groups, N))
    C = jax.random.normal(keys[2], (G, P, groups, N))
    dt = jax.random.uniform(keys[3], (G, P, nh), minval=0.01, maxval=0.5)
    A = -jax.random.uniform(keys[4], (nh,), minval=0.5, maxval=8.0)
    live = np.arange(P)[None, :] < np.asarray(lengths)[:, None]
    dt = jnp.where(live[..., None], dt, 0.0)
    y, state = mamba2.ssd_chunked(x, dt, A, B, C, CHUNK)
    want_y, want_state = _sequential(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state), want_state,
                               rtol=2e-4, atol=2e-4)
    for g, n in enumerate(lengths):          # and it IS the row's own
        _, alone = _sequential(x[g:g + 1, :n], dt[g:g + 1, :n], A,
                               B[g:g + 1, :n], C[g:g + 1, :n])
        np.testing.assert_allclose(np.asarray(state)[g], alone[0],
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------- the state-update kernel
@pytest.mark.parametrize("active", [
    (0, 1, 1, 0, 1, 0), (1, 0, 0, 0, 0, 0), (0, 0, 0, 0, 0, 0),
    (1, 1, 1, 1, 1, 1), (0, 0, 0, 0, 0, 1)],
    ids=["mixed", "first-only", "none", "all", "last-only"])
@pytest.mark.parametrize("dtype,groups", [
    (jnp.bfloat16, 1), (jnp.float32, 1), (jnp.float32, 2)],
    ids=["bf16", "f32", "f32-two-groups"])
def test_state_update_kernel_advances_the_active_slots_alone(active, dtype,
                                                             groups):
    """``ops/ssm_state_update.py`` (interpreted here) against its own XLA
    form, which is the arithmetic written out: the active slots' layer is
    advanced, every other slot and every other layer is BIT FOR BIT what
    it was -- whichever slots are active, none of them, or only one at
    either end (the kernel maps a slot that does not advance to another
    slot's block: ``_plan``).  With two groups of B and C each half of the
    lanes is fed and read by its own, in the kernel's chunks as in the
    einsum."""
    from ray_tpu.ops import ssm_state_update as op

    layers, slots, n, hd = 3, 6, 16, 64
    keys = jax.random.split(jax.random.key(sum(active)), 5)
    ssm = jax.random.normal(keys[0], (layers, slots, n, hd)).astype(dtype)
    decay = jax.random.uniform(keys[1], (slots, hd))
    dtx = jax.random.normal(keys[2], (slots, hd))
    b = jax.random.normal(keys[3], (slots, groups, n))
    c = jax.random.normal(keys[4], (slots, groups, n)).astype(
        jnp.bfloat16).astype(jnp.float32)
    on = jnp.asarray(active, bool)
    got, y = op.ssm_state_update(ssm, jnp.int32(1), on, decay, dtx, b, c)
    want, want_y = op._xla_update(ssm, jnp.int32(1), on, decay, dtx, b, c)
    # an ulp of the storage type: the two fuse their multiply-adds apart
    ulp = 2.0 ** -7 if dtype == jnp.bfloat16 else 1e-6
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=ulp, atol=ulp)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=16 * ulp, atol=16 * ulp)
    for other in (0, 2):                      # the layers not asked for
        np.testing.assert_array_equal(np.asarray(got, np.float32)[other],
                                      np.asarray(ssm, np.float32)[other])
    off = ~np.asarray(on)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32)[1][off],
        np.asarray(ssm, np.float32)[1][off])
    assert not np.asarray(y)[off].any()
    if on.any():
        assert (np.asarray(got, np.float32)[1][np.asarray(on)]
                != np.asarray(ssm, np.float32)[1][np.asarray(on)]).any()


# ------------------------------------- the programs against the reference
def _serve(cfg, params, prompts, slots, steps, tamper=None, engine_params=None,
           sit_out=(), k=4, tamper_every_chunk=False):
    """Prefill ``prompts`` as one right-padded group into ``slots`` of an
    empty cache, then ``steps`` greedy tokens a slot in chunks of 4
    through ``build_decode_k``.  ``tamper(cache) -> cache`` runs between
    the two (and after every chunk of ``k`` if asked); ``sit_out``: slots
    that are not active in the FIRST chunk (and run one chunk more
    instead).  Returns ({slot: emitted tokens}, cache)."""
    run = engine_params if engine_params is not None else params
    prefill, decode_k = family.programs(cfg)
    cache = llama_serve.init_cache(cfg, SLOTS, MAX_LEN)
    toks, lengths = _padded(prompts, 24)
    cache, first, _ = prefill(run, cache, toks, lengths,
                              jnp.asarray(slots, jnp.int32))
    if tamper is not None:
        cache = tamper(cache)
    tok = np.zeros(SLOTS, np.int32)
    lens = np.zeros(SLOTS, np.int32)
    emitted = {}
    for i, slot in enumerate(slots):
        tok[slot], lens[slot] = int(first[i]), len(prompts[i])
        emitted[slot] = [int(first[i])]
    tok, lens = jnp.asarray(tok), jnp.asarray(lens)
    zeros, no = jnp.zeros(SLOTS, jnp.int32), jnp.zeros(SLOTS, bool)
    chunks = -(-steps // k) + (1 if sit_out else 0)
    for chunk in range(chunks):
        active = np.zeros(SLOTS, bool)
        active[[s for s in slots if not (chunk == 0 and s in sit_out)]] = True
        cache, out, tok, lens, _ = decode_k(
            run, cache, tok, lens, zeros, zeros, no, jnp.asarray(active),
            k=k, s_active=MAX_LEN)
        if tamper_every_chunk:
            cache = tamper(cache)
        for slot in np.flatnonzero(active):
            emitted[slot].extend(int(t) for t in np.asarray(out)[:, slot])
    return {s: e[:steps + 1] for s, e in emitted.items()}, cache


def _worst_gap(cfg, params, prompts, slots, emitted):
    return max(float(reference.teacher_forced_gap(
        params, prompt, emitted[slot], _published(cfg), pad_to=64).max())
        for prompt, slot in zip(prompts, slots))


def test_prefill_then_decode_against_the_full_forward_pass(model):
    """Prompts of 1, 7, 8, 9 and 20 tokens in two right-padded groups (a
    chunk is 8: inside, on the edge, past it, several), prefilled into
    slots out of order, then 12 tokens each through the cache in chunks of
    4: at every emitted position the reference's logit of the emitted
    token lies within TOL deviations of its top logit."""
    cfg, params = model
    for lengths, slots in (((1, 7, 20), (2, 0, 3)), ((8, 9), (1, 2))):
        prompts = _prompts(sum(lengths), lengths)
        emitted, _ = _serve(cfg, params, prompts, slots, steps=12)
        gap = _worst_gap(cfg, params, prompts, slots, emitted)
        assert gap <= TOL, gap
        assert len({t for e in emitted.values() for t in e}) > 12


@pytest.mark.parametrize("heads,kv_heads", [(8, 4), (4, 2)])
def test_heads_of_64_lie_two_a_row_and_read_as_the_full_forward_pass(
        heads, kv_heads):
    """Granite 4.0-H's own heads are 64 wide: the pool keeps two a 128-lane
    row, as rows, and prefill's insert, the step's write and the widened
    query give what the reference's full forward pass gives -- GQA with two
    rows a position, and with one."""
    cfg, params = _model(_cfg(n_heads=heads, n_kv_heads=kv_heads,
                              head_dim=64, attention_multiplier=1.0 / 64))
    cache = jax.eval_shape(lambda: llama_serve.init_cache(cfg, SLOTS,
                                                          MAX_LEN))
    assert cache["k"].shape == cache["v"].shape == (
        2, SLOTS, MAX_LEN * kv_heads // 2, 128)
    assert llama_serve.kv_rows(cfg, cache) == {
        "kv_row_heads": kv_heads // 2, "kv_row_dim": 128,
        "decode_attention": "kernel"}
    lengths, slots = (1, 7, 20), (2, 0, 3)
    prompts = _prompts(sum(lengths), lengths)
    emitted, _ = _serve(cfg, params, prompts, slots, steps=12)
    gap = _worst_gap(cfg, params, prompts, slots, emitted)
    assert gap <= TOL, gap


def test_reference_deviation_is_what_the_gap_divides_by(model):
    """The toy's logits, like the published widths', are divided by
    ``logits_scaling``: their deviation is far from 1, and
    ``teacher_forced_gap`` is in units of it."""
    cfg, params = model
    tokens = jnp.asarray(_prompts(3, (24,)))
    sigma = reference.logit_deviation(params, tokens, _published(cfg))
    assert 0.003 < sigma < 0.03
    lg = np.asarray(reference.logits(params, tokens, _published(cfg)))[0]
    emitted = [int(np.argsort(lg[-1])[-2])]          # the runner-up
    gap = reference.teacher_forced_gap(params, tokens[0].tolist(), emitted,
                                       _published(cfg))
    top2 = np.sort(lg[-1])[-2:]
    np.testing.assert_allclose(gap[0], (top2[1] - top2[0]) / lg[-1].std(),
                               rtol=1e-4)


def test_a_slot_that_sits_out_a_chunk_keeps_its_state(model):
    """A slot that is not active for a chunk (its request waits for its
    first token while others decode) has both states bit for bit as they
    were, and then emits the tokens it would have."""
    cfg, params = model
    prompts, slots = _prompts(5, (9, 13, 6)), (0, 1, 3)
    _, before = _serve(cfg, params, prompts, slots, steps=0)
    apart, after = _serve(cfg, params, prompts, slots, steps=4, sit_out=(1,))
    together, _ = _serve(cfg, params, prompts, slots, steps=4)
    assert apart == together
    # after ONE chunk in which slot 1 sat out: run it alone to look
    decode_k = family.programs(cfg)[1]
    zeros, no = jnp.zeros(SLOTS, jnp.int32), jnp.zeros(SLOTS, bool)
    cache, *_ = decode_k(params, jax.tree.map(jnp.copy, before),
                         jnp.asarray([apart[0][0], 0, 0, apart[3][0]],
                                     jnp.int32),
                         jnp.asarray([9, 13, 0, 6], jnp.int32), zeros, zeros,
                         no, jnp.asarray([True, False, False, True]), k=4,
                         s_active=MAX_LEN)
    for name, axis in (("ssm", 1), ("conv", 2)):
        got, was = np.asarray(cache[name]), np.asarray(before[name])
        for slot in (1, 2):                  # sat out; never held anything
            np.testing.assert_array_equal(got.take(slot, axis),
                                          was.take(slot, axis))
        assert (got.take(0, axis) != was.take(0, axis)).any()
    assert _worst_gap(cfg, params, prompts, slots, apart) <= TOL


def test_a_reused_slot_inherits_nothing(model):
    """A prefill replaces a slot's whole state: after a long request in
    the slot, a one-token prompt decodes exactly as in an empty cache."""
    cfg, params = model
    long, short = _prompts(7, (20,)), _prompts(8, (1,))
    _, used = _serve(cfg, params, long, (2,), steps=8)
    prefill = family.programs(cfg)[0]
    toks, lengths = _padded(short, 24)
    slot = jnp.asarray([2], jnp.int32)
    again, first_a, _ = prefill(params, used, toks, lengths, slot)
    fresh, first_b, _ = prefill(
        params, llama_serve.init_cache(cfg, SLOTS, MAX_LEN), toks, lengths,
        slot)
    assert int(first_a[0]) == int(first_b[0])
    for name, axis in (("ssm", 1), ("conv", 2)):
        np.testing.assert_array_equal(
            np.asarray(again[name]).take(2, axis),
            np.asarray(fresh[name]).take(2, axis))
    emitted, _ = _serve(cfg, params, short, (2,), steps=8)
    assert _worst_gap(cfg, params, short, (2,), emitted) <= TOL


# ------------------------------------------ broken variants fail the check
def _swap_state_rows(cache):
    ssm = cache["ssm"]
    return {**cache, "ssm": ssm.at[:, 0].set(ssm[:, 3]).at[:, 3].set(
        ssm[:, 0])}


def _int8_state(cache):
    """Each layer's states rounded to 255 levels of the layer's largest
    value: what an int8 store with a scale a tensor would keep."""
    ssm = cache["ssm"]
    scale = jnp.max(jnp.abs(ssm), axis=(1, 2, 3), keepdims=True) / 127.0
    return {**cache, "ssm": jnp.round(ssm / jnp.maximum(scale, 1e-30))
            * scale}


@pytest.mark.parametrize("variant", ["wrong-state-row", "inactive-advanced",
                                     "dropped-conv-tap", "int8-state"])
def test_a_broken_variant_fails_the_reference(model, variant):
    """What ``correct`` has to catch, caught at toy size: each variant's
    tokens are OVER the benchmark's margin against the reference, which
    the intact programs are far under."""
    cfg, params = model
    prompts, slots = _prompts(11, (20, 15, 9)), (0, 3, 1)
    kw = {}
    if variant == "wrong-state-row":
        kw["tamper"] = _swap_state_rows
    elif variant == "int8-state":       # stored as int8: after every step
        kw.update(tamper=_int8_state, k=1, tamper_every_chunk=True)
    elif variant == "dropped-conv-tap":
        w = params["layers"]["ssm_conv_w"]
        kw["engine_params"] = {**params, "layers": {
            **params["layers"], "ssm_conv_w": w.at[:, 0].set(0.0)}}
    if variant == "inactive-advanced":
        # slot 3's request waits for its first token through one chunk; a
        # step that advanced its state all the same is this: the chunk run
        # with the slot active on whatever token its carry holds
        emitted, _ = _serve(cfg, params, prompts, slots, steps=12)
        decode_k = family.programs(cfg)[1]
        _, cache = _serve(cfg, params, prompts, slots, steps=0)
        zeros, no = jnp.zeros(SLOTS, jnp.int32), jnp.zeros(SLOTS, bool)
        lens = np.asarray([20, 9, 0, 15], np.int32)
        stale = jnp.asarray([0, 0, 0, 7], jnp.int32)
        only3 = jnp.asarray([False, False, False, True])
        cache, *_ = decode_k(params, cache, stale, jnp.asarray(lens), zeros,
                             zeros, no, only3, k=4, s_active=MAX_LEN)
        tok = jnp.asarray([0, 0, 0, emitted[3][0]], jnp.int32)
        _, out, *_ = decode_k(params, cache, tok, jnp.asarray(lens), zeros,
                              zeros, no, only3, k=12, s_active=MAX_LEN)
        emitted = {3: [emitted[3][0]] + [int(t) for t in
                                         np.asarray(out)[:, 3]]}
        prompts, slots = prompts[1:2], (3,)
    else:
        emitted, _ = _serve(cfg, params, prompts, slots, steps=24, **kw)
    gap = _worst_gap(cfg, params, prompts, slots, emitted)
    assert gap > MARGIN, gap


# ------------------------------------------------- through the scheduler
_presets = family.presets({"hybrid_debug_f32": _cfg})
engine = family.engines("hybrid_debug", max_len=MAX_LEN)


def test_llm_server_serves_the_hybrid_through_generate(model, engine):
    """``LLMServer.generate`` on the dense plane, no option: admission,
    prefill waves, chunks, slots reused by later requests (8 requests on
    4 slots) -- every reply within TOL of the reference."""
    cfg, params = model
    family.serves_through_generate(
        engine(params=params, model_preset="hybrid_debug_f32"),
        ((5, 9), (16, 12), (23, 7), (1, 14), (30, 6), (8, 10), (9, 5),
         (17, 11)),
        lambda prompt, tokens: reference.teacher_forced_gap(
            params, prompt, tokens, _published(cfg), pad_to=64).max(), TOL)


@pytest.mark.parametrize("plane,args", family.PLANES)
def test_planes_that_cannot_hold_a_state_refuse_the_config(plane, args):
    """Blocks, shared prefixes, a rejected draft's rewind, a K/V hand-off
    and K/V quantization all rest on a cache of rows by position; a
    recurrent state is not one.  Each refuses at construction, naming the
    reason and what was asked."""
    family.refuses_plane("hybrid_debug", plane, args, "state-space",
                         words=("not rows by position",))


def test_training_refuses_the_config(model):
    cfg, params = model
    with pytest.raises(NotImplementedError, match="served only"):
        llama.forward(params, jnp.zeros((1, 8), jnp.int32), cfg)


def test_spans_counters_and_pools_for_a_hybrid_and_only_for_one(engine):
    """``serve.chunk`` carries ``state_rows_updated`` / ``state_bytes``,
    ``serve.prefill_group`` ``scan_chunks``, the series
    ``ray_tpu_serve_state_bytes_total{kind}`` counts the same bytes, and
    ``kv_stats()`` / the pool gauges say what the cache holds; an engine
    of a plain decoder emits none of it."""
    assert tracing.enabled()
    group = metrics.serve_engine_counters()
    pools = metrics.kv_cache_counters()

    def series():
        return {kind: group["state_bytes"].snapshot().get(("llm", kind), 0.0)
                for kind in ("ssm", "conv")}

    timeline.clear()
    before = series()
    # servers of its own, this one and the plain one below: every span on
    # the timeline is counted, and the last chunk's is written by the time
    # the scheduler's thread has been joined (``shutdown``)
    server = engine(fresh=True)
    cfg = server.cfg
    requests = [{"prompt": list(range(1, 1 + n)), "max_new_tokens": 6}
                for n in (5, 9, 20)]
    family.generate(server, requests)
    stats = server.kv_stats()
    server.shutdown()
    per_slot = llama_serve.state_bytes_per_slot(cfg)
    # 4 Mamba layers x (16 x (4 heads x 16) float32 | 3 taps x 96 bfloat16)
    assert per_slot == {"ssm": 4 * 4 * 16 * 16 * 4,
                        "conv": 4 * 3 * (64 + 32) * 2}
    spans = timeline.export_timeline()
    groups = family.span_args(spans, "serve.prefill_group")
    chunks = family.span_args(spans, "serve.chunk")
    assert groups and chunks
    for g in groups:        # buckets 16 and 32 in chunks of 8
        assert g["scan_chunks"] == g["rows_padded"] * g["bucket"] // 8
        # Mamba-2 layers, no KDA and no Mamba-1 layer: nothing of the
        # delta rule's kernel nor of the selective scan's
        assert "kda_chunk_positions" not in g
        assert "mamba1_scan_positions" not in g
    for c in chunks:
        assert c["state_rows_updated"] == c["active"] * c["k"]
        assert c["state_bytes"] == 2 * c["state_rows_updated"] * sum(
            per_slot.values())
    moved = {kind: series()[kind] - before[kind] for kind in before}
    rows = sum(c["state_rows_updated"] for c in chunks)
    assert moved == {kind: 2.0 * rows * per_slot[kind] for kind in per_slot}
    assert stats["state_pool"]["ssm_bytes"] == 4 * per_slot["ssm"]
    assert stats["state_pool"]["conv_bytes"] == 4 * per_slot["conv"]
    assert stats["state_pool"]["kv_bytes"] == 2 * 2 * 4 * 128 * 2 * 16 * 2
    assert stats["state_pool"]["ssm_dtype"] == "float32"
    assert pools["state_pool_bytes"].snapshot()[
        ("llm", "ssm", "float32")] == 4 * per_slot["ssm"]
    assert pools["pool_bytes"].snapshot()[("llm", "bfloat16")] \
        == stats["state_pool"]["kv_bytes"]

    # a plain decoder of the dense cells' shape: none of it
    timeline.clear()
    after = series()
    dense = engine(model_preset="debug", fresh=True)
    family.generate(dense, requests[:2])
    assert "state_pool" not in dense.kv_stats()
    dense.shutdown()
    for e in timeline.export_timeline():
        if e.get("name") in ("serve.chunk", "serve.prefill_group"):
            assert not {"state_rows_updated", "state_bytes",
                        "scan_chunks"} & set(e["args"])
    assert series() == after

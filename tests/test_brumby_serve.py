"""Brumby's shape at toy widths through the dense serving plane, held to
``benchmarks/references/brumby_decoder.py`` (float32, the ATTENTION form of
power retention, a block of queries against every key): three layers of
power retention at degree 2 -- 4 query heads in groups of 2 over 2
key/value heads of 16, so D = 136 (144 rows as laid out), q/k head norms and
RoPE inside the mixer, a scalar gate a key/value head -- and NO attending
layer: a serving cache without a K or V leaf.
"""

import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.lib import power_flops, power_state
from benchmarks.references import brumby_decoder as reference
from benchmarks.tools import power_check
from ray_tpu.models import llama, llama_serve, power_retention
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import power_chunk
from ray_tpu.ops import power_state_update as op

VOCAB, SLOTS, MAX_LEN = 256, 4, 64
TOL = 1e-3          # float32 both sides: the order of sums alone
SHIFT = (1.0, 4.0)  # a memory of 2 to 30 positions: a toy row's length


def _cfg(**kw):
    base = dict(
        vocab_size=VOCAB, hidden_size=64, n_layers=3, n_heads=4,
        n_kv_heads=2, head_dim=16, intermediate_size=128,
        max_seq_len=MAX_LEN, rope_theta=1e6, norm_eps=1e-6,
        tie_embeddings=False, remat=False, dtype=jnp.float32,
        layer_pattern=("power",), power_chunk=8, power_gate_shift=SHIFT)
    base.update(kw)
    return LlamaConfig(**base)


def _published(cfg):
    """The toy configuration in the published key names (what the
    reference and the yardstick read)."""
    return {
        "model_type": "brumby", "num_hidden_layers": cfg.n_layers,
        "hidden_size": cfg.hidden_size,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "vocab_size": cfg.vocab_size, "rms_norm_eps": cfg.norm_eps,
        "intermediate_size": cfg.intermediate_size,
        "max_position_embeddings": MAX_LEN, "rope_theta": cfg.rope_theta,
        "tie_word_embeddings": False, "power_degree": 2,
        "power_eps": cfg.power_eps, "gate_shift": list(SHIFT),
        "dtype": {"serve": "float32", "power_state": "float32"},
        "program_fields": {
            "layer_pattern": ["power"], "power_chunk": cfg.power_chunk,
            "power_gate_shift": list(SHIFT), "dtype": "float32",
            "remat": False}}


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return (cfg, family.init_params(jax.random.key(7), cfg, jnp.float32),
            _published(cfg))


def _gap(params, prompt, emitted, published):
    return float(reference.teacher_forced_report(
        params, prompt, emitted, published)["gap"].max())


# ------------------------------------------------ config, tree and cache
def test_the_config_its_parameters_and_a_cache_without_kv(model):
    cfg, params, published = model
    assert cfg.period == ("power",)
    assert (cfg.layers_of("power"), cfg.attending_layers()) == (3, 0)
    assert not cfg.plain_decoder and not cfg.one_kv_stack
    assert llama.param_count(params) == power_flops.parameters(published)
    layers = params["layers"]
    assert not set(layers) & set(llama.ATTENTION_LEAVES)
    assert layers["power_q"].shape == (3, 64, 64)
    assert layers["power_k"].shape == layers["power_v"].shape == (3, 64, 32)
    assert layers["power_g"].shape == (3, 64, 2)
    assert layers["power_q_norm"].shape == (3, 16)
    assert set(llama.param_logical_axes(cfg)["layers"]) == set(layers)
    # no K, no V: one state leaf, whatever max_len is
    served = _cfg(dtype=jnp.bfloat16)
    for max_len in (64, 4096):
        cache = jax.eval_shape(
            lambda: llama_serve.init_cache(served, 3, max_len))
        assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
            "ssm": ((3, 3, 2, 10, 16, 16), "float32")}
    assert op.state_rows(16) == 144 and len(reference.triangle(16)[0]) == 136
    pools = llama_serve.cache_pools(served, 3, 64)
    assert pools == {"ssm": (3 * 3 * 2 * 10 * 16 * 16 * 4, "float32")}
    assert llama_serve.state_bytes_per_slot(served) \
        == {"ssm": pools["ssm"][0] // 3}
    # the algorithm's count is of the triangle's 136 rows, not the 144 + 16
    assert power_flops.slot_bytes(
        dict(published, dtype={"serve": "bfloat16",
                               "power_state": "float32"})) \
        == 3 * 2 * (136 * 16 + 136) * 4
    assert llama_serve.kv_rows(served, None) == {}
    assert llama_serve.kv_rows(
        served, llama_serve.init_cache(served, 1, 8)) == {}
    assert llama_serve.share_and_state(served) == {
        "state_bytes_per_slot": pools["ssm"][0] // 3,
        "power_state_rows": 144, "power_degree": 2, "attending_layers": 0}
    with pytest.raises(NotImplementedError, match="served only"):
        llama.forward(None, jnp.zeros((1, 4), jnp.int32), cfg)
    with pytest.raises(ValueError, match="degree 2"):
        _cfg(power_chunk=12)
    with pytest.raises(ValueError, match="do not mix"):
        _cfg(layer_pattern=("power", "kda"), n_layers=4, kda_heads=2)


def test_phi_of_q_dot_phi_of_k_is_the_square_of_q_dot_k():
    q, k = jax.random.normal(jax.random.key(0), (2, 5, 16))
    got = jnp.sum(op.phi(q) * op.phi(k), axis=(-1, -2))
    np.testing.assert_allclose(got, jnp.sum(q * k, -1) ** 2, rtol=1e-4,
                               atol=1e-5)
    # and in the reference's own order, through the stored layout
    a, b, w = reference.triangle(16)
    state = op.join_state(
        jnp.broadcast_to(op.phi(k)[..., None, :], (5, 9, 16, 16)),
        op.phi(k))
    S, z = power_state.to_triangle(np.asarray(state))
    np.testing.assert_allclose(z, np.asarray(k[:, a] * k[:, b]) * w,
                               rtol=1e-5)
    np.testing.assert_allclose(S[:, :, 3], z, rtol=1e-6)


# ----------------------------------------------- engine against reference
def test_the_walk_is_the_reference_at_every_position(model):
    """Logits, every position of rows of 40 (five chunks of 8): the chunked
    state form against the attention form."""
    cfg, params, published = model
    tokens = np.random.default_rng(1).integers(0, VOCAB, (2, 40))
    mine = llama.layer_walk(params, jnp.asarray(tokens, jnp.int32), cfg,
                            None)[0]
    theirs = reference.logits(params, tokens, published)
    assert float(jnp.std(theirs)) > 0.3
    np.testing.assert_allclose(mine, theirs, atol=2e-4)


@pytest.mark.parametrize("chunk,positions", [(8, 40), (16, 37)],
                         ids=["chunks-of-8", "chunks-of-16-ragged"])
def test_the_chunked_form_is_the_recurrence(chunk, positions):
    """``power_chunk`` against ``_xla_update`` token by token, from a state
    that is not empty: whole chunks, and a last chunk that the row's length
    ends inside (the padding neither decays nor writes)."""
    ks = jax.random.split(jax.random.key(chunk), 6)
    T = -(-positions // chunk) * chunk
    q = jax.random.normal(ks[0], (2, T, 4, 16))
    k = jax.random.normal(ks[1], (2, T, 2, 16))
    v = jax.random.normal(ks[2], (2, T, 2, 16))
    gamma = jax.nn.log_sigmoid(2.0 + jax.random.normal(ks[3], (2, T, 2)))
    live = (jnp.arange(T) < positions)[None, :, None]
    k, gamma = jnp.where(live[..., None], k, 0.0), jnp.where(live, gamma, 0.0)
    start = op.join_state(
        jax.random.normal(ks[4], (2, 2, 9, 16, 16)),
        5.0 + jnp.abs(jax.random.normal(ks[5], (2, 2, 9, 16))))
    o, end = power_chunk.power_chunk(q, k, v, gamma, start, chunk)
    def step(state, x):
        return op._xla_update(state, jnp.int32(0), jnp.ones(2, bool), *x)

    state, o_t = jax.lax.scan(step, start[None], tuple(
        jnp.moveaxis(x, 1, 0)[:positions]
        for x in (jnp.exp(gamma), q, k, v)))
    np.testing.assert_allclose(o[:, :positions], jnp.moveaxis(o_t, 0, 1),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(end, state[0], rtol=2e-4, atol=2e-4)


def test_the_chunk_kernel_interpreted_is_the_xla_form(monkeypatch):
    """``ops/power_chunk.py``'s kernel at a head Mosaic tiles (d = 128, 5
    readers a head), interpreted: three chunks of 16 from a state that is
    not empty, the row's length ending inside the last; against
    ``_xla_chunks``."""
    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "_use_interpret", lambda: True)
    assert power_chunk.engages(128, 16) and not power_chunk.engages(16, 8)
    ks = jax.random.split(jax.random.key(1), 6)
    q = jax.random.normal(ks[0], (1, 48, 5, 128))
    k, v = jax.random.normal(ks[1], (2, 1, 48, 1, 128))
    gamma = jax.nn.log_sigmoid(3.0 + jax.random.normal(ks[2], (1, 48, 1)))
    live = (jnp.arange(48) < 41)[None, :, None]
    k, gamma = jnp.where(live[..., None], k, 0.0), jnp.where(live, gamma, 0.0)
    start = op.join_state(
        0.1 * jax.random.normal(ks[3], (1, 1, 65, 128, 128)),
        50.0 + jnp.abs(jax.random.normal(ks[4], (1, 1, 65, 128))))
    want_o, want_state = power_chunk._xla_chunks(q, k, v, gamma, start, 16,
                                                 op.EPS)
    got_o, got_state = power_chunk.power_chunk(q, k, v, gamma, start, 16)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_state, want_state, rtol=1e-5, atol=1e-4)


def test_the_kernel_interpreted_is_the_xla_update(monkeypatch):
    """``ops/power_state_update.py``'s kernel at a state Mosaic tiles (d =
    128, 5 readers a head, as published), interpreted: an inactive slot in
    front of the first active one, one between two, the layer named by the
    operand; against ``_xla_update``."""
    flash = importlib.import_module("ray_tpu.ops.flash_attention")
    monkeypatch.setattr(flash, "_use_interpret", lambda: True)
    ks = jax.random.split(jax.random.key(0), 6)
    state = jax.random.normal(ks[0], (2, 4, 1, 66, 128, 128)) * 0.1
    S, z = op.split_state(state)
    state = op.join_state(S, 50.0 * jnp.abs(z))
    q = jax.random.normal(ks[1], (4, 5, 128))
    k, v = jax.random.normal(ks[2], (2, 4, 1, 128))
    decay = jax.random.uniform(ks[3], (4, 1), minval=0.5, maxval=1.0)
    active = jnp.asarray([False, True, False, True])
    want_state, want_o = op._xla_update(state, jnp.int32(1), active, decay,
                                        q, k, v)
    got_state, got_o = jax.jit(op.power_state_update)(
        state, jnp.int32(1), active, decay, q, k, v)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got_state, want_state, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(got_o[0]).max()) == 0.0
    np.testing.assert_array_equal(got_state[0], state[0])
    np.testing.assert_array_equal(got_state[1, 2], state[1, 2])


def test_prefill_then_decode_through_the_state_is_the_reference(model):
    """Three prompts of unlike lengths in ONE padded group (lengths inside
    a chunk, a padding row behind them), then decoded together through the
    states, one sitting out a chunk in the middle: every emitted position
    of each within TOL of the reference's full forward pass; the prefill's
    own logits are the reference's numbers at each row's last position;
    and the slot's first-layer state is the sum as it is written."""
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
    assert got[1] is None and got[2] is None          # no K, no V
    (state,) = got[4]
    assert state.shape == (3, 4, 2, 10, 16, 16)
    assert float(jnp.abs(state[:, 3]).max()) == 0.0      # the padding row

    cache = llama_serve.init_cache(cfg, SLOTS, MAX_LEN)
    cache, first, load = family.prefill(cfg, params, cache, prompts, slots)
    assert set(cache) == {"ssm"} and load == ()
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
    # slot 3 has taken 30 + 16 tokens in: every layer against the sum
    seq = list(prompts[2]) + emitted[3][:-1]
    S, z = reference.state_sums(params, seq, published, len(seq))
    assert S.shape == (3, 2, 136, 16) and z.shape == (3, 2, 136)
    deviation = power_state.deviation(
        *power_state.to_triangle(np.asarray(cache["ssm"][:, 3])), S, z)
    assert max(max(layer) for layer in deviation["head"]) < 1e-5, deviation
    assert max(deviation["whole"]) < 1e-5


def test_the_gate_has_no_shift_unless_the_config_names_one(model):
    """``power_gate_shift`` stands for a trained gate under RANDOM weights:
    no default of the program (a checkpoint's config names none, and its
    gate is its weights alone), and without it the forward is the
    reference's without ``gate_shift``."""
    _cfg_shifted, params, published = model
    assert LlamaConfig(layer_pattern=("power",)).power_gate_shift is None
    bare = _cfg(power_gate_shift=None)
    toks = np.random.default_rng(6).integers(0, VOCAB, (1, 16))
    got = llama.prefill_with_states(
        params, jnp.asarray(toks, jnp.int32), jnp.asarray([16], jnp.int32),
        bare)[0][0]
    unshifted = {k: v for k, v in published.items() if k != "gate_shift"}
    want = reference.logits(params, toks, unshifted)[0, -1]
    assert float(jnp.abs(got - want).max()) <= TOL
    shifted = reference.logits(params, toks, published)[0, -1]
    assert float(jnp.abs(got - shifted).max()) > 10 * TOL


def test_the_cells_own_comparison_reads_the_states_too(model, engine,
                                                       capsys):
    """``teacher_forced_gap`` as the cell's ``correct`` calls it, handed the
    weights and a reply: the gaps of a sound reply as read, and the states
    the LIVE engine's own programs leave in a slot of its own cache (the
    request once more through them, whole chunks, both slots advancing) in
    every layer against the sum as written; an infinite gap in front where
    a head lies past the limit; refused without a running engine."""
    cfg, params, published = model
    # a server of its own: the check drives THE running engine's programs
    server = engine(params=params, fresh=True)
    prompt = np.random.default_rng(4).integers(0, VOCAB, 21).tolist()
    (reply,) = family.generate(server, [{"prompt": prompt,
                                         "max_new_tokens": 10}])
    capsys.readouterr()
    sound = reference.teacher_forced_gap(params, prompt, reply["tokens"],
                                         published, pad_to=MAX_LEN)
    assert len(sound) == 10 and float(sound.max()) <= TOL
    (line,) = [json.loads(text) for text in
               capsys.readouterr().out.strip().splitlines()]
    # 9 tokens behind the first: two whole chunks of 4
    assert line["state_of"] == {
        "slot": 31 % 2, "slots": 2, "k": 4, "positions": 21 + 8,
        "tokens_are_the_replys": True}
    heads = line["state_deviation"]["head"]
    assert len(heads) == 3 and max(max(layer) for layer in heads) < 1e-5
    # the engine is as it was: it serves on
    (again,) = family.generate(server, [{"prompt": prompt,
                                         "max_new_tokens": 10}])
    assert again["tokens"] == reply["tokens"]
    # the first layer by its furthest head, a later one by its whole state
    far = [0.001, 2 * reference.STATE_LIMIT]
    for whole, head, refused in (
            ([0.0, 0.0], [far, [0.0]], True), ([0.0, 0.0], [[0.0], far], False),
            ([0.0, 2 * reference.LATER_STATE_LIMIT], [[0.0], [0.0]], True),
            ([2 * reference.LATER_STATE_LIMIT, 0.0], [[0.0], [0.0]], False)):
        got = reference.judged(sound, {"whole": whole, "head": head})
        assert np.isinf(got[0]) == refused and len(got) == 10 + refused
    server.shutdown()
    with pytest.raises(RuntimeError, match="0 running"):
        reference.teacher_forced_gap(params, prompt, reply["tokens"],
                                     published, pad_to=MAX_LEN)


@pytest.mark.parametrize("variant", power_check.VARIANTS)
def test_a_broken_variant_fails_the_reference(model, variant):
    """The same weights under a program that is wrong in one place
    (``benchmarks/tools/power_check.py`` runs the same variants at the
    published widths on the chip), LOGITS against the reference's at every
    position of a 32-token row, 24 prefilled and 8 through the state."""
    cfg, params, published = model
    tokens = np.random.default_rng(2).integers(0, VOCAB, (1, 32))
    vcfg, patched = power_check.broken(variant, cfg)
    family.forget_programs()
    with patched():
        distance = power_check.logit_distance(
            vcfg, params, tokens, published, prompt=24, max_len=MAX_LEN)
    if variant == "intact":
        assert distance <= TOL
    else:
        assert distance > 10 * TOL, distance


# ------------------------------------------------------------- the engine
_presets = family.presets({"brumby_toy": _cfg})
engine = family.engines("brumby_toy", max_slots=2, max_len=MAX_LEN,
                        prefill_groups=(1, 2))


def test_llm_server_serves_the_model_and_counts_the_state_it_moves(
        model, traced, engine):
    cfg, params, published = model
    # two slots and groups of one and two rows: of three requests the third
    # is served in a REUSED slot.  A server of its own: every chunk on the
    # timeline is counted, and it is shut down before they are.
    server = engine(params=params, fresh=True)
    assert server.decode_buckets == (MAX_LEN,)     # nothing is attended
    assert set(server.cache) == {"ssm"}
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
    build = family.span_args(events, "serve.engine_build")[-1]
    state = 3 * 2 * 10 * 16 * 16 * 4
    assert {k: build[k] for k in ("state_bytes_per_slot", "power_state_rows",
                                  "power_degree", "attending_layers")} \
        == {"state_bytes_per_slot": state, "power_state_rows": 144,
            "power_degree": 2, "attending_layers": 0}
    assert "kv_row_heads" not in build and "decode_attention" not in build
    chunks = family.span_args(events, "serve.chunk")
    assert chunks
    for c in chunks:
        assert c["power_slots_advanced"] == c["k"] * c["active"] \
            == c["state_rows_updated"]
        assert c["power_state_bytes"] == c["state_bytes"] \
            == 2 * c["power_slots_advanced"] * state
    assert pools["state_pool"]["bytes_per_slot"] == {"ssm": state}
    groups = family.span_args(events, "serve.prefill_group")
    assert groups
    for g in groups:
        padded = power_retention.padded_len(g["bucket"], cfg.power_chunk)
        # (a toy head of 16 keeps XLA's form: the kernel's count is 0)
        assert g["power_chunk_positions"] == (
            g["rows_padded"] * padded * 3
            if power_chunk.engages(cfg.head_dim, cfg.power_chunk) else 0) \
            == 0
        assert g["scan_chunks"] == g["rows_padded"] * padded // 8
    from ray_tpu.observability import device, metrics

    assert {"power_gate", "power_chunk", "power_state_update"} \
        <= set(device.SCOPES)
    counters = metrics.serve_engine_counters()
    assert counters["power_slots_advanced"].name \
        == "ray_tpu_serve_power_slots_advanced_total"
    assert counters["power_chunk_positions"].name \
        == "ray_tpu_serve_power_chunk_positions_total"


@pytest.mark.parametrize("plane,args", family.PLANES,
                         ids=[p for p, _ in family.PLANES])
def test_the_planes_built_on_kv_rows_refuse_the_model(plane, args):
    family.refuses_plane("brumby_toy", plane if plane != "disaggregat"
                         else "disaggregation", args, "power-retention",
                         words=("state",))

"""A decoder-hybrid-decoder (SambaY: Phi-4-mini-flash-reasoning's shape)
through the serving programs, at toy size on the CPU: 8 layers ``m w m w |
m A | g c`` -- Mamba-1 and window layers, ONE K/V layer, a gated memory unit
and a cross layer that reads the K/V layer's rows --, hidden 64, window 8,
scan chunks of 4, differential attention, LayerNorm with a bias.

The reference is ``benchmarks/references/phi4flash_decoder.py``: float32,
a sequential recurrence, two explicit softmaxes a differential head, every
layer at every position.  Kernels interpreted.
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.lib import sambay_flops
from benchmarks.references import phi4flash_decoder as reference
from ray_tpu.models import llama, llama_serve, mamba1
from ray_tpu.models.llama import LlamaConfig

flash = importlib.import_module("ray_tpu.ops.flash_attention")

KINDS = ("mamba1", "window", "mamba1", "window", "mamba1", "attention",
         "gmu", "cross")
# the reference's view of the same model: the published key names
PUBLISHED = dict(num_hidden_layers=8, mb_per_layer=2, layer_norm_eps=1e-5,
                 num_attention_heads=8, num_key_value_heads=4,
                 hidden_size=64, sliding_window=8, tie_word_embeddings=True,
                 intermediate_size=128, vocab_size=256, head_dim=8,
                 mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                 mamba_dt_rank=4,
                 dtype={"serve": "float32", "ssm_state": "float32"})


def toy(**fields) -> LlamaConfig:
    base = dict(n_layers=8, layer_types=KINDS, hidden_size=64, n_heads=8,
                n_kv_heads=4, head_dim=8, window_size=8, ssm_inner=128,
                ssm_state=16, ssm_dt_rank=4, ssm_conv=4, ssm_chunk=4,
                rope=False, diff_attention=True, layer_norm=True,
                attn_bias=True, stream_dtype=jnp.float32, dtype=jnp.float32,
                max_seq_len=64)
    base.update(fields)
    return LlamaConfig.debug(**base)


@pytest.fixture(scope="module")
def model():
    cfg = toy()
    return cfg, family.init_params(jax.random.key(0), cfg, jnp.float32)


@pytest.fixture(scope="module")
def tokens():
    return np.asarray(jax.random.randint(jax.random.key(1), (2, 40), 1, 256))


def _window_step(cfg):
    return lambda q, k, v, pos: (
        llama.dot_attention(q, k, v, pos, cfg.attn_scale, cfg.window_size),
        (k, v))


def _gaps(params, prompt, emitted):
    return reference.teacher_forced_gap(params, prompt, emitted, PUBLISHED)


# ------------------------------------------------------------ the config
def test_the_stack_cuts_at_the_kv_layer_and_refuses_what_is_not_built():
    cfg = toy()
    assert cfg.kv_layer == 5 and (cfg.kv_row_heads, cfg.kv_row_dim) == (2, 16)
    assert [(key, l0, part.n_layers, part.period, part.layer_offset)
            for part, key, l0 in cfg.parts()] == [
        ("layers", 0, 4, ("mamba1", "window"), 0),
        ("layers_1", 4, 1, ("mamba1",), 4),
        ("layers_2", 5, 1, ("attention",), 5),
        ("layers_3", 6, 2, ("gmu", "cross"), 6)]
    assert not cfg.plain_decoder and not cfg.one_kv_stack
    # the published model: three scans and the K/V layer
    full = ("mamba1", "window") * 8 + ("mamba1", "attention") \
        + ("gmu", "cross") * 7
    assert [(l0, part.period, part.n_layers) for part, _, l0 in dataclasses.
            replace(cfg, n_layers=32, layer_types=full).parts()] == [
        (0, ("mamba1", "window"), 16), (16, ("mamba1",), 1),
        (17, ("attention",), 1), (18, ("gmu", "cross"), 14)]
    for kinds, why in (
            (("cross",) + KINDS[1:], "no attention layer before"),
            (("gmu", "window", "mamba1", "window", "mamba1", "attention",
              "gmu", "cross"), "gmu layer"),
            (KINDS[:6] + ("window", "cross"), "only gmu and cross")):
        with pytest.raises(ValueError, match=why):
            toy(layer_types=kinds)
    with pytest.raises(ValueError, match="one kind of state-keeping"):
        toy(layer_types=("mamba",) + KINDS[1:], ssm_heads=2)
    with pytest.raises(ValueError, match="differential attention"):
        toy(rope=True)
    with pytest.raises(NotImplementedError, match="served only"):
        llama.forward(None, jnp.zeros((1, 4), jnp.int32), cfg)


def test_the_parameters_by_hand(model):
    """A layer's leaves by hand, against ``init_params`` and the
    yardstick's own count -- at toy size, and at the published sizes to the
    unit (3,852,562,944)."""
    cfg, params = model
    h, f, di, n, r, d = 64, 128, 128, 16, 4, 8
    mlp, norms = 3 * h * f, 4 * h
    mamba = h * 2 * di + (4 + 1) * di + di * (r + 2 * n) + r * di + di \
        + n * di + di + di * h
    attention = h * (64 + 32 + 32) + (64 + 32 + 32) + 64 * h + h + 4 * d \
        + 2 * d
    cross = 2 * (h * 64 + 64) + 4 * d + 2 * d
    gmu = 2 * h * di
    by_hand = 3 * mamba + 3 * attention + cross + gmu \
        + 8 * (mlp + norms) + 256 * h + 2 * h
    assert llama.param_count(params) == by_hand \
        == sambay_flops.parameters(PUBLISHED)
    published = dict(PUBLISHED, num_hidden_layers=32, hidden_size=2560,
                     num_attention_heads=40, num_key_value_heads=20,
                     head_dim=64, intermediate_size=10240, vocab_size=200064,
                     mamba_dt_rank=160, sliding_window=512)
    assert sambay_flops.mixer_matmul_params(published)["mamba"] \
        + sambay_flops.mixer_small_params(published)["mamba"] == 41_241_600
    assert sambay_flops.parameters(published) == 3_852_562_944


def test_the_cache_holds_its_pools_side_by_side():
    cfg = toy(dtype=jnp.bfloat16)
    cache = jax.eval_shape(lambda: llama_serve.init_cache(cfg, 3, 64))
    assert {k: (v.shape, v.dtype.name) for k, v in cache.items()} == {
        "k": ((1, 3, 64 * 2, 16), "bfloat16"),
        "v": ((1, 3, 64 * 2, 16), "bfloat16"),
        "wk": ((2, 3, 8 * 2, 16), "bfloat16"),
        "wv": ((2, 3, 8 * 2, 16), "bfloat16"),
        "ssm": ((3, 3, 16, 128), "float32"),
        "conv": ((3, 3, 3, 128), "bfloat16")}
    pools = llama_serve.cache_pools(cfg, 3, 64)
    per_slot = sambay_flops.slot_bytes(
        dict(PUBLISHED, dtype={"serve": "bfloat16", "ssm_state": "float32"}),
        64)
    assert {k: v[0] for k, v in pools.items()} \
        == {k: 3 * v for k, v in per_slot.items()}
    assert llama_serve.state_bytes_per_slot(cfg) \
        == {k: per_slot[k] for k in ("ssm", "conv")}


# --------------------------------------------------------------- the walk
def test_the_walk_is_the_reference_at_every_position(model, tokens):
    cfg, params = model
    mine = llama.layer_walk(params, jnp.asarray(tokens), cfg, None,
                            window_step=_window_step(cfg))[0]
    theirs = reference.logits(params, tokens, PUBLISHED)
    assert float(jnp.std(theirs)) > 0.5
    np.testing.assert_allclose(mine, theirs, atol=5e-5)


def test_the_prefill_that_stops_at_the_kv_layer_gives_what_the_whole_walk_does(
        model, tokens):
    """With ``lengths`` the cross-decoder runs at each row's last real
    position alone: its logits, the K/V layer's rows and the states are
    those of the walk that runs every layer at every position."""
    cfg, params = model
    lengths = jnp.asarray([40, 23])
    whole = llama.layer_walk(params, jnp.asarray(tokens), cfg, None,
                             window_step=_window_step(cfg))
    last, ks, vs, rows, states, window, index_keys = \
        llama.prefill_with_states(params, jnp.asarray(tokens), lengths, cfg)
    assert rows is None and index_keys is None
    for row, n in enumerate((40, 23)):
        np.testing.assert_allclose(last[row], whole[0][row, n - 1],
                                   atol=2e-5)
        for mine, theirs in ((ks, whole[1][0]), (vs, whole[1][1])):
            assert mine.shape == (1, 2, 40, 2, 16)
            np.testing.assert_allclose(mine[0, row, :n],
                                       theirs[0, row, :n], atol=1e-5)
    # row 0 is whole: its states are the unskipped walk's
    (state, conv), (whole_state, whole_conv) = states, whole[3]
    assert state.shape == (3, 2, 16, 128) and conv.shape == (3, 3, 2, 128)
    np.testing.assert_allclose(state[:, 0], whole_state[:, 0], atol=1e-5)
    np.testing.assert_allclose(conv[:, :, 0], whole_conv[:, :, 0], atol=1e-6)
    assert window[0].shape == (2, 2, 40, 2, 16)


def test_the_chunked_scan_is_the_sequential_recurrence(model):
    """``mamba1.prefill`` (chunks of 4 over 10 positions: an edge inside,
    a ragged end) against ``mamba1.decode`` a token at a time, for rows
    that end at 10, 7 (padding inside a chunk) and 4 (on an edge): the
    output, the memory, and both states as of each row's last position."""
    cfg, params = model
    part = cfg.parts()[0][0]
    layer = {k: v[1] for k, v in params["layers"].items()
             if k.startswith("ssm_")}
    h = jax.random.normal(jax.random.key(3), (3, 10, 64))
    lengths = jnp.asarray([10, 7, 4])
    out, (state, conv), memory = mamba1.prefill(h, layer, part, lengths)
    ssm = jnp.zeros((1, 3, 16, 128))
    window = jnp.zeros((1, 3, 3, 128))
    for t in range(10):
        step, ssm, window, y = mamba1.decode(
            h[:, t:t + 1], layer, part, ssm, window, 0, t < lengths)
        for row in range(3):
            if t < int(lengths[row]):
                np.testing.assert_allclose(out[row, t], step[row, 0],
                                           atol=1e-5)
                np.testing.assert_allclose(memory[row, t], y[row, 0],
                                           atol=1e-5)
    np.testing.assert_allclose(state, ssm[0], atol=1e-5)
    np.testing.assert_allclose(conv, window[0], atol=1e-6)


def test_the_kernels_compute_the_two_explicit_softmaxes(model, monkeypatch):
    """Differential attention through the kernels the engine runs -- the
    banded flash forward over padded queries and rows of two heads, the
    decode kernel over a ring and over the K/V layer's pool -- against the
    reference's two explicit softmaxes a head."""
    from ray_tpu.ops.decode_attention import decode_attention

    cfg, params = model
    monkeypatch.setattr(flash, "DEFAULT_BLOCK", 16)
    S, B = 32, 2
    layer = {k: v[0] for k, v in params["layers_2"].items()}
    part = cfg.parts()[2][0]
    x = jax.random.normal(jax.random.key(5), (B, S, 64))
    q, k, v = llama._qkv_rope(x, layer, None, None, part)
    assert q.shape == (B, S, 8, 16) and k.shape == v.shape == (B, S, 2, 16)
    w = {name: leaf.astype(jnp.float32) for name, leaf in layer.items()}
    h = reference._layer_norm(x, w["attn_norm"], w["attn_norm_bias"], 1e-5)
    plain = [h @ w["w" + n] + w["b" + n] for n in "qkv"]
    pos = jnp.arange(S)

    def explicit(row, window):
        visible = pos[None, :] <= pos[:, None]
        if window:
            visible &= pos[:, None] - pos[None, :] < window
        return reference._differential(
            plain[0][row], plain[1][row], plain[2][row], visible, w, 5,
            8, 4, 8, 1e-5)

    for window in (None, 8):
        attn = flash.flash_prefill_attention(
            q, k, v, scale=cfg.attn_scale, window=window,
            lengths=jnp.asarray([S, S]))
        mine = llama.diff_combine(attn, layer, 5, part).reshape(B, S, 64)
        for row in range(B):
            np.testing.assert_allclose(mine[row], explicit(row, window),
                                       atol=2e-5)
    # decode: the last position's query against the pool as stored
    pool_k = k.reshape(1, B, S * 2, 16)
    pool_v = v.reshape(1, B, S * 2, 16)
    attn = decode_attention(
        q[:, -1], pool_k, pool_v, 0, jnp.asarray([S - 1, S - 1]),
        jnp.ones(B, bool), s_active=S, scale=cfg.attn_scale, hkv=2)
    mine = llama.diff_combine(attn[:, None], layer, 5, part).reshape(B, 64)
    for row in range(B):
        np.testing.assert_allclose(mine[row], explicit(row, None)[-1],
                                   atol=2e-5)


# ------------------------------------------------------------ the engine
# two slots and groups of one and two rows: three requests put the third
# in a REUSED slot
_presets = family.presets({
    "phi4flash_toy": toy,
    "phi4flash_wide": lambda **kw: toy(ssm_inner=1024, **kw)})
engine = family.engines("phi4flash_toy", max_slots=2, max_len=64,
                        prefill_groups=(1, 2))


def test_prefill_then_decode_through_the_cache_is_the_reference(
        model, tokens, traced, monkeypatch, engine):
    """Through ``LLMServer``: three prompts on two slots (9 tokens: one
    ring lap; 20: past the window and a scan chunk's edge; 31), 12 tokens
    each, so the third request is served in a REUSED slot and inherits no
    state, ring row or memory; the flash forward prefills the longer
    bucket.  Every emitted token is the reference's leading one to within
    float32 rounding.  The spans carry the skipped positions, the shared
    pool's reads and the positions the scan's kernel took."""
    cfg, params = model
    monkeypatch.setattr(llama, "FLASH_PREFILL_FROM", 16)
    monkeypatch.setattr(flash, "DEFAULT_BLOCK", 16)
    prompts = [tokens[0, :9].tolist(), tokens[1, :20].tolist(),
               tokens[0, 5:36].tolist()]
    # a server of its own: its programs are traced under this test's
    # patches, and every span on the timeline is counted
    server = engine(params=params, fresh=True)
    replies = family.generate(
        server, [{"prompt": p, "max_new_tokens": 12} for p in prompts])
    family.settle(server)
    server.shutdown()
    for prompt, reply in zip(prompts, replies):
        assert len(reply["tokens"]) == 12
        assert float(np.max(_gaps(params, prompt, reply["tokens"]))) < 1e-3
    events = traced.export_timeline()
    groups = family.span_args(events, "serve.prefill_group")
    chunks = family.span_args(events, "serve.chunk")
    assert groups and chunks
    for g in groups:
        # layers 6 and 7 at one position a row
        assert g["layers"] == 8
        assert g["positions_skipped"] == g["rows_padded"] \
            * (g["bucket"] - 1) * 2
    for c in chunks:
        # two layers read the one pool: the K/V layer and the cross layer
        assert c["kv_full_layers"] == 2 and c["kv_window_layers"] == 2
        assert c["shared_kv_positions_attended"] \
            == 2 * c["kv_positions_attended"]
        assert c["state_rows_updated"] == c["k"] * c["active"]
    pools = server.kv_stats()["kv_pools"]
    assert set(pools) >= {"kv_full", "kv_window"}
    # the toy's 128 channels keep XLA's loop: no position went through
    # ``ops/mamba1_scan.py`` (the kernel's side: the test below)
    assert all(g["mamba1_scan_positions"] == 0 for g in groups)
    assert "pallas_call" not in _mixers_jaxpr(cfg, 20)


def _mixers_jaxpr(cfg, bucket, rows=1):
    """What ``mamba1.prefill`` traces to for ``rows`` rows of ``bucket``
    positions: the program text that says which form of the scan a prefill
    of this geometry holds."""
    part = cfg.parts()[0][0]
    params = jax.eval_shape(
        lambda k: llama.init_params(k, cfg, jnp.float32), jax.random.key(0))
    layer = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
             for k, v in params["layers"].items() if k.startswith("ssm_")}
    return str(jax.make_jaxpr(
        lambda h, layer, lengths: mamba1.prefill(h, layer, part, lengths))(
            jax.ShapeDtypeStruct((rows, bucket, cfg.hidden_size),
                                 jnp.float32),
            layer, jax.ShapeDtypeStruct((rows,), jnp.int32)))


def test_an_engine_whose_channels_are_whole_blocks_serves_through_the_kernel(
        tokens, traced, engine):
    """1,024 channels, ONE block of ``ops/mamba1_scan.py``: the same engine's
    prefill program holds the kernel, its tokens are the reference's, and
    ``serve.prefill_group`` counts the positions the kernel covered -- the
    bucket in whole groups of 8 x the three Mamba-1 layers, from a real
    launch (the span's rule and the program's are one ``engages``).  A group
    of several rows keeps the loop (PERF.md section 6 (g), PR 62)."""
    from ray_tpu.ops import mamba1_scan

    cfg = toy(ssm_inner=1024)
    assert mamba1_scan.engages(cfg.ssm_inner, 1)
    params = family.init_params(jax.random.key(0), cfg, jnp.float32)
    # a server of its own: another preset, one slot, one bucket of 20
    server = engine(model_preset="phi4flash_wide", params=params,
                    max_slots=1, prefill_groups=(1,), prefill_buckets=(20,),
                    fresh=True)
    prompt = tokens[1, :13].tolist()
    reply, = family.generate(server, [{"prompt": prompt,
                                       "max_new_tokens": 6}])
    family.settle(server)
    server.shutdown()
    gaps = reference.teacher_forced_gap(
        params, prompt, reply["tokens"], {**PUBLISHED, "mamba_expand": 16})
    assert float(np.max(gaps)) < 1e-3
    text = _mixers_jaxpr(cfg, 20)
    assert "pallas_call" in text and "mamba1_scan" in text
    assert not mamba1_scan.engages(cfg.ssm_inner, 2)
    assert "pallas_call" not in _mixers_jaxpr(cfg, 20, rows=2)
    groups = family.span_args(traced.export_timeline(),
                              "serve.prefill_group")
    assert mamba1_scan.padded_len(20) == 24
    assert groups and all(
        g["mamba1_scan_positions"] == g["rows_padded"] * 24 * 3
        and g["bucket"] == 20 for g in groups)


def _walk_distance(cfg, params, tokens):
    """The walk's logits against the reference's, largest over positions, in
    units of the logits' deviation."""
    mine = llama.layer_walk(params, jnp.asarray(tokens), cfg, None,
                            window_step=_window_step(cfg))[0]
    theirs = reference.logits(params, tokens, PUBLISHED)
    return float(jnp.max(jnp.abs(mine - theirs)) / jnp.std(theirs))


def test_faults_read_far_over_the_sound_engine(model, tokens, monkeypatch,
                                               engine):
    """How tight the comparison is at THIS size.  The sound programs sit
    1e-5 deviations from the reference.  Lambda applied to the pair's first
    softmax makes the emitted tokens arbitrary ones: the harness's own
    reading (the reference's top logit less its logit of the emitted
    token) is over its 0.25 limit.  The two rounding faults -- the two
    softmaxes' results subtracted in bfloat16, the recurrent state stored
    in bfloat16 -- move the logits by 1e-3 to 1e-2 deviations, a hundred
    times the sound programs' distance, which flips no leading token of a
    256-row vocabulary in a few dozen positions: the limit-sized reading
    of those two is the chip's, at the published widths and a 200,064-row
    vocabulary (``benchmarks/tools/sambay_check.py``; PERF.md section 6,
    PR 49)."""
    from benchmarks.tools import sambay_check

    cfg, params = model
    sound = _walk_distance(cfg, params, tokens)
    assert sound < 1e-4
    # lambda on the wrong half, through the engine's tokens
    prompt = tokens[1, :20].tolist()
    with monkeypatch.context() as patch:
        patch.setattr(llama, "diff_combine",
                      sambay_check.faulty_combine("lambda_wrong_half"))
        # a server of its own: its programs are traced under the fault
        replies = family.generate(
            engine(params=params, fresh=True),
            [{"prompt": prompt, "max_new_tokens": 12}])
        wrong_half = _walk_distance(cfg, params, tokens)
    assert float(np.max(_gaps(params, prompt, replies[0]["tokens"]))) > 0.25
    assert wrong_half > 0.25
    # the subtraction in the attention's own type, bfloat16 as on the chip
    with monkeypatch.context() as patch:
        patch.setattr(llama, "diff_combine",
                      sambay_check.faulty_combine("diff_bf16"))
        rounded = _walk_distance(
            dataclasses.replace(cfg, dtype=jnp.bfloat16),
            jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), tokens)
    in_bf16 = _walk_distance(
        dataclasses.replace(cfg, dtype=jnp.bfloat16),
        jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), tokens)
    print("sound", sound, "bf16 engine", in_bf16, "with the fault", rounded)
    assert 100 * sound < in_bf16 < rounded < 0.25
    # the state stored in bfloat16: 30 decode steps of one Mamba-1 layer
    part = cfg.parts()[0][0]
    layer = {k: v[0] for k, v in params["layers"].items()
             if k.startswith("ssm_")}
    h = jax.random.normal(jax.random.key(4), (2, 30, 64))

    def last_memory(dtype):
        ssm = jnp.zeros((1, 2, 16, 128), dtype)
        window = jnp.zeros((1, 3, 2, 128))
        for t in range(30):
            _out, ssm, window, y = mamba1.decode(
                h[:, t:t + 1], layer, part, ssm, window, 0,
                jnp.ones(2, bool))
        return y

    exact, stored = last_memory(jnp.float32), last_memory(jnp.bfloat16)
    # (9e-4 of the memory's deviation; float32 steps agree to 1e-6)
    assert float(jnp.max(jnp.abs(stored - exact)) / jnp.std(exact)) > 3e-4


def test_the_planes_that_hold_kv_rows_alone_refuse_the_model():
    family.refuses_plane("phi4flash_toy", "paged", dict(paged=True),
                         "keep a state a slot")

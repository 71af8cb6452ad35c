"""The KDA mixer (``ray_tpu/models/kda.py``), its prefill kernel
(``ray_tpu/ops/kda_chunk.py``) and its decode kernel
(``ray_tpu/ops/kda_state_update.py``) against the recurrence as it is
written, token by token, in float32."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kda, llama
from ray_tpu.ops import kda_chunk as chunk_op
from ray_tpu.ops import kda_state_update as op


def _recurrence(q, k, v, g, b, state):
    """S_t = (I - b k k^T) Diag(e^g) S_{t-1} + b k v^T; o_t = S_t^T q_t.
    q, k, v, g (N, T, H, d), b (N, T, H), state (N, H, d, d)."""
    def step(S, x):
        q, k, v, g, b = x
        S = jnp.exp(g)[..., None] * S
        r = jnp.einsum("nhk,nhkv->nhv", k, S, precision="highest")
        S = S + b[..., None, None] * k[..., None] * (v - r)[..., None, :]
        return S, jnp.einsum("nhk,nhkv->nhv", q, S, precision="highest")

    state, o = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1), state


# one program a shape (as ``_kernel_inputs`` below): op by op the draws
# are a dozen small compiles a case
@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _inputs(seed, N, T, H, d, strong_decay=1.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    q = kda._l2norm(jax.random.normal(ks[0], (N, T, H, d))) * d ** -0.5
    k = kda._l2norm(jax.random.normal(ks[1], (N, T, H, d)))
    v = jax.random.normal(ks[2], (N, T, H, d))
    # a log-decay a channel from slow to fast: the fastest fall by ~4 a
    # step, 250 a chunk of 64, where e^-G overflows float32
    rate = jnp.exp(jax.random.uniform(
        ks[3], (N, 1, H, d), minval=jnp.log(1e-3), maxval=jnp.log(4.0)))
    g = -strong_decay * rate * jax.random.uniform(
        jax.random.fold_in(ks[3], 1), (N, T, H, d), minval=0.5, maxval=1.5)
    b = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (N, T, H)))
    S = 0.3 * jax.random.normal(ks[5], (N, H, d, d))
    return q, k, v, g, b, S


@pytest.mark.parametrize("T,chunk,d", [(128, 64, 32), (96, 32, 16)])
def test_the_chunked_rule_is_the_recurrence(T, chunk, d):
    q, k, v, g, b, S = _inputs(T + d, 2, T, 3, d)
    assert float(b.max()) > 1.5                  # negative eigenvalues
    # inside a chunk e^-G would overflow
    assert float(jnp.cumsum(g[:, :chunk], 1).min()) < -100 or chunk < 64
    want_o, want_S = jax.jit(_recurrence)(q, k, v, g, b, S)
    got_o, got_S = jax.jit(kda.chunk_rule, static_argnums=6)(
        q, k, v, g, b, S, chunk)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got_S, want_S, atol=2e-5, rtol=2e-5)


def test_a_padded_position_neither_decays_nor_writes():
    """g = 0 and b = 0 from a row's length on: the state is that of the
    last real position."""
    q, k, v, g, b, S = _inputs(3, 2, 64, 2, 16)
    lengths = jnp.array([37, 64])
    live = jnp.arange(64)[None, :] < lengths[:, None]
    g = jnp.where(live[..., None, None], g, 0.0)
    b = jnp.where(live[..., None], b, 0.0)
    _, got = jax.jit(kda.chunk_rule, static_argnums=6)(q, k, v, g, b, S, 16)
    _, want = jax.jit(_recurrence)(q[:1, :37], k[:1, :37], v[:1, :37], g[:1, :37],
                          b[:1, :37], S[:1])
    np.testing.assert_allclose(got[:1], want, atol=2e-5, rtol=2e-5)


def _chunk_kernel(*args, chunk=64):
    # a jit of its own a call: the heads a step are read at trace time
    return jax.jit(lambda *a: chunk_op.kda_chunk(*a, chunk))(*args)


@pytest.mark.parametrize("T,heads_a_step", [(64, 2), (128, 2), (1024, 1)])
def test_the_chunk_kernel_is_the_rule_is_the_recurrence(T, heads_a_step,
                                                        monkeypatch):
    """Interpret mode, at the smallest state Mosaic tiles (d = 128) in the
    cell's chunks of 64, two blocks of heads, from a non-zero state, over
    1, 2 and 16 chunks: ``o`` and the state are the recurrence's and
    ``chunk_rule``'s, with decays that fall by > 100 a chunk (where
    ``e^-G`` overflows) and ``b`` past 1.5."""
    H = 2 * heads_a_step
    q, k, v, g, b, S = _inputs(T, 1, T, H, 128)
    assert float(b.max()) > 1.5
    assert float(jnp.cumsum(g[:, :64], 1).min()) < -100
    assert chunk_op.engages(128, 64)
    monkeypatch.setattr(chunk_op, "_HEADS", heads_a_step)
    got_o, got_S = _chunk_kernel(q, k, v, g, b, S)
    want_o, want_S = jax.jit(_recurrence)(q, k, v, g, b, S)
    xla_o, xla_S = jax.jit(kda.chunk_rule, static_argnums=6)(
        q, k, v, g, b, S, 64)
    for want in ((want_o, want_S), (xla_o, xla_S)):
        np.testing.assert_allclose(got_o, want[0], atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(got_S, want[1], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("lengths", [(37, 128), (64, 100), (1, 65)])
def test_the_chunk_kernel_neither_decays_nor_writes_at_a_padded_position(
        lengths, monkeypatch):
    """Rows padded from different lengths: a row's state is that of ITS
    last real position -- the recurrence's over the real positions, and for
    a row that ends inside the first chunk bit for bit what a call over
    that chunk alone returns."""
    monkeypatch.setattr(chunk_op, "_HEADS", 1)
    q, k, v, g, b, S = _inputs(11, 2, 128, 1, 128)
    at = jnp.asarray(lengths)
    live = jnp.arange(128)[None, :] < at[:, None]
    g = jnp.where(live[..., None, None], g, 0.0)
    b = jnp.where(live[..., None], b, 0.0)
    got_o, got_S = _chunk_kernel(q, k, v, g, b, S)
    for row, n in enumerate(lengths):
        want_o, want_S = jax.jit(_recurrence)(
            *(x[row:row + 1, :n] for x in (q, k, v, g, b)), S[row:row + 1])
        np.testing.assert_allclose(got_S[row:row + 1], want_S, atol=2e-5,
                                   rtol=2e-5)
        np.testing.assert_allclose(got_o[row:row + 1, :n], want_o,
                                   atol=2e-5, rtol=2e-5)
    # the first chunk alone, both rows: the second chunk of a row that
    # ended before it changes nothing
    _, short = _chunk_kernel(*(x[:, :64] for x in (q, k, v, g, b)), S)
    ended = np.asarray(lengths) <= 64
    assert ended.any()
    np.testing.assert_array_equal(got_S[ended], short[ended])


@pytest.mark.parametrize("d,chunk", [(16, 16), (32, 64), (128, 24),
                                     (128, 128)])
def test_a_chunk_mosaic_cannot_tile_keeps_the_xla_form(d, chunk):
    """By shape: a state that is not whole 128-lane tiles, a chunk that is
    not whole sub-blocks or leaves the kernel no room: ``chunk_rule``'s own
    arrays, exactly."""
    assert not chunk_op.engages(d, chunk)
    q, k, v, g, b, S = _inputs(9, 1, 2 * chunk, 2, d)
    got = _chunk_kernel(q, k, v, g, b, S, chunk=chunk)
    want = jax.jit(kda.chunk_rule, static_argnums=6)(q, k, v, g, b, S, chunk)
    for a, w in zip(got, want):
        np.testing.assert_array_equal(a, w)


def _config(**kw):
    return llama.LlamaConfig(**{**dict(
        vocab_size=64, hidden_size=32, n_layers=4, n_heads=4, n_kv_heads=2,
        head_dim=8, intermediate_size=64, max_seq_len=256,
        layer_pattern=("attention", "kda", "kda", "kda"), rope=False,
        kda_heads=2, kda_head_dim=16, kda_gate_rank=8, kda_chunk=16,
        dtype=jnp.float32), **kw})


def _layer(c, seed=0):
    params = kda.init_params(jax.random.key(seed), c, 1, jnp.float32,
                             lambda k, s, f: llama.init_dense(k, s, f))
    return jax.tree.map(lambda x: x[0], params)


@pytest.mark.parametrize("P,lengths", [(64, (64, 23)), (40, (40, 17)),
                                       (16, (1, 16))])
def test_prefill_then_decode_is_one_recurrence(P, lengths):
    """``prefill`` over a prompt, then ``decode`` token by token from the
    states it hands over, against ``prefill`` over the whole sequence: the
    hand-over is at each row's true length, inside a chunk or at its end,
    and a bucket that is not whole chunks is padded."""
    c = _config()
    layer = _layer(c)
    total = P + 6
    h = jax.random.normal(jax.random.key(1), (2, total, c.hidden_size))
    want, _ = kda.prefill(h, layer, c, None)
    lengths = jnp.array(lengths)
    out, (state, conv) = kda.prefill(h[:, :P], layer, c, lengths)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(out[row, :n], want[row, :n], atol=1e-5,
                                   rtol=1e-4)
    ssm, conv = state[None], conv[None]
    active = jnp.array([True, True])
    for step in range(6):
        at = lengths + step
        tok = jnp.take_along_axis(h, at[:, None, None], axis=1)
        out, ssm, conv = kda.decode(tok, layer, c, ssm, conv,
                                    jnp.int32(0), active)
        for row in range(2):
            np.testing.assert_allclose(out[row, 0], want[row, at[row]],
                                       atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("P,lengths", [(64, (64, 23)), (70, (64, 3))])
def test_prefill_then_decode_is_one_recurrence_through_the_kernels(
        P, lengths):
    """The same hand-over at a state Mosaic tiles (d = 128, chunks of 64):
    ``prefill`` through ``ops/kda_chunk.py``, ``decode`` through
    ``ops/kda_state_update.py``, both interpreted."""
    c = _config(kda_head_dim=128, kda_chunk=64)
    assert chunk_op.engages(c.kda_head_dim, c.kda_chunk)
    layer = _layer(c)
    total = P + 3
    h = jax.random.normal(jax.random.key(1), (2, total, c.hidden_size))
    want, _ = kda.prefill(h, layer, c, None)
    lengths = jnp.array(lengths)
    out, (state, conv) = kda.prefill(h[:, :P], layer, c, lengths)
    for row, n in enumerate(lengths):
        np.testing.assert_allclose(out[row, :n], want[row, :n], atol=1e-5,
                                   rtol=1e-4)
    ssm, conv = state[None], conv[None]
    active = jnp.array([True, True])
    for step in range(3):
        at = lengths + step
        tok = jnp.take_along_axis(h, at[:, None, None], axis=1)
        out, ssm, conv = kda.decode(tok, layer, c, ssm, conv,
                                    jnp.int32(0), active)
        for row in range(2):
            np.testing.assert_allclose(out[row, 0], want[row, at[row]],
                                       atol=1e-5, rtol=1e-4)


def test_b_reaches_past_one_on_the_drawn_weights():
    """``b = 2 sigmoid(.)``: about half of the drawn weights' values lie in
    (1, 2), where ``I - b k k^T`` has a negative eigenvalue."""
    c = _config()
    layer = _layer(c)
    h = jax.random.normal(jax.random.key(2), (1, 256, c.hidden_size))
    _, low = kda._project(h, layer, c)
    *_, b, _z = kda._heads(jnp.zeros((1, 256, kda.dims(c)[1])), low, layer,
                           c, jnp.ones((1, 256, 1), bool))
    assert 0.25 < float((b > 1.0).mean()) < 0.75
    assert float(b.max()) > 1.4 and float(b.min()) > 0.0


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _kernel_inputs(seed, slots, H, d, layers=2):
    ks = jax.random.split(jax.random.key(seed), 7)
    ssm = jax.random.normal(ks[0], (layers, slots, H, d, d))
    q = kda._l2norm(jax.random.normal(ks[1], (slots, H, d))) * d ** -0.5
    k = kda._l2norm(jax.random.normal(ks[2], (slots, H, d)))
    v = jax.random.normal(ks[3], (slots, H, d))
    decay = jnp.exp(-jnp.exp(jax.random.uniform(
        ks[4], (slots, H, d), minval=jnp.log(1e-3), maxval=jnp.log(3.0))))
    b = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[5], (slots, H)))
    return ssm, q, k, v, decay, b


_update = jax.jit(op.kda_state_update)


@pytest.mark.parametrize("active", [
    (True, False, True, True, False), (False, False, True, False, True),
    (False,) * 5, (True,) * 5])
def test_the_kernel_is_the_xla_form_is_the_recurrence(active, monkeypatch):
    """Interpret mode, at the smallest state Mosaic tiles (d = 128), two
    blocks of heads: an active slot's state and output are the
    recurrence's, an inactive slot's state is bit-identical and its output
    zero; layer 0 is not touched."""
    slots, H, d = 5, 16, 128
    ssm, q, k, v, decay, b = _kernel_inputs(7, slots, H, d)
    active = jnp.array(active)
    assert op._heads_a_block(H, d) == 16
    monkeypatch.setattr(op, "_HEADS", 8)     # two blocks of heads a slot
    got_ssm, got_o = _update(ssm, jnp.int32(1), active, decay, q, k, v, b)
    xla_ssm, xla_o = op._xla_update(ssm, jnp.int32(1), active, decay, q, k,
                                    v, b)
    want_o, want_S = _recurrence(
        q[:, None], k[:, None], v[:, None], jnp.log(decay)[:, None],
        b[:, None], ssm[1])
    on = np.asarray(active)
    for got_s, o in ((got_ssm, got_o), (xla_ssm, xla_o)):
        np.testing.assert_array_equal(got_s[0], ssm[0])
        np.testing.assert_array_equal(got_s[1][~on], ssm[1][~on])
        np.testing.assert_array_equal(o[~on], 0.0)
        np.testing.assert_allclose(got_s[1][on], want_S[on], atol=1e-5,
                                   rtol=1e-5)
        np.testing.assert_allclose(o[on], want_o[on, 0], atol=1e-5,
                                   rtol=1e-5)


def test_a_state_mosaic_cannot_tile_is_updated_by_xla():
    ssm, q, k, v, decay, b = _kernel_inputs(9, 3, 2, 16, layers=1)
    active = jnp.array([True, False, True])
    got = op.kda_state_update(ssm, jnp.int32(0), active, decay, q, k, v, b)
    want = op._xla_update(ssm, jnp.int32(0), active, decay, q, k, v, b)
    for a, w in zip(got, want):
        np.testing.assert_array_equal(a, w)


def test_kda_is_imported_where_a_layer_asks_for_it():
    """Another model's start does not pay for this one: none of the three
    modules is loaded by the modules every engine imports, and the two
    kernels' only where ``kda.prefill`` / ``kda.decode`` ask for them."""
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, ray_tpu.serve.llm, ray_tpu.models.llama_serve;"
         "print([m for m in sys.modules if 'kda' in m]);"
         "import ray_tpu.models.kda;"
         "print([m for m in sys.modules if 'kda' in m])"],
        capture_output=True, text=True, check=True,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.split("\n")[:2] == ["[]", "['ray_tpu.models.kda']"], \
        out.stdout


def test_a_config_is_refused_what_it_cannot_serve():
    with pytest.raises(ValueError, match="kda_heads"):
        _config(kda_heads=0)
    with pytest.raises(ValueError, match="do not mix"):
        llama.LlamaConfig.debug(
            layer_pattern=("kda", "mamba"), n_layers=2, kda_heads=2,
            ssm_heads=2)
    c = _config(attn_gate=True)
    assert not c.plain_decoder and not c.one_kv_stack
    assert llama.state_mixer("kda")[0] is kda

"""Kernel correctness: flash + ring attention vs the einsum reference
(interpret mode on CPU; the same code paths run compiled on TPU)."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import dot_attention
from ray_tpu.ops import flash_attention, ring_attention
from ray_tpu.parallel import MeshSpec, use_mesh


def _jit(fn, **static):
    """``fn`` with its keywords bound, as ONE program: op by op a
    reference and its gradient are a hundred small compiles a case."""
    return jax.jit(functools.partial(fn, **static))


def _normal(seed, shape, dtype=jnp.float32):
    """Made on the host: ``jax.random.normal`` is a program a shape, and
    what the values are is nothing a test here reads."""
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        shape, np.float32).astype(dtype))


def _rand_qkv(seed, B, S, Hq, Hkv, D, dtype=jnp.float32):
    return (_normal(seed, (B, S, Hq, D), dtype),
            _normal(seed + 100, (B, S, Hkv, D), dtype),
            _normal(seed + 200, (B, S, Hkv, D), dtype))


def _positions(B, S):
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))


def _dot_attention(q, k, v):
    B, S = q.shape[:2]
    return dot_attention(q, k, v, _positions(B, S))


def _gqa_reference(q, k, v, *, causal):
    """Plain float32 attention in the kernels' layout, q (B, Hq, S, D)
    pre-scaled, k/v (B, Hkv, S, D): ``(o, lse)``.  Differentiating it
    gives dk/dv per KV head (the repeat's transpose sums the group)."""
    S, group = q.shape[2], q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(x, group, axis=1) for x in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k)
    if causal:
        s = jnp.where(jnp.tri(S, dtype=bool), s, -jnp.inf)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v),
            jax.nn.logsumexp(s, axis=-1))


@functools.partial(jax.jit, static_argnames="causal")
def _reference_and_vjp(q, k, v, do, *, causal):
    """``_gqa_reference``'s (o, lse) and its gradients under ``do``."""
    (o, lse), vjp = jax.vjp(
        functools.partial(_gqa_reference, causal=causal), q, k, v)
    return o, lse, vjp((do, jnp.zeros_like(lse)))


@pytest.mark.parametrize("B,S,Hq,Hkv,D", [
    (2, 128, 4, 4, 64),     # MHA
    (2, 256, 8, 2, 32),     # GQA 4:1
    (1, 128, 4, 1, 64),     # MQA
])
def test_flash_forward_matches_reference(B, S, Hq, Hkv, D):
    q, k, v = _rand_qkv(0, B, S, Hq, Hkv, D)
    ref = jax.jit(_dot_attention)(q, k, v)
    out = _jit(flash_attention, causal=True, block_q=64, block_k=128)(
        q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_backward_matches_reference():
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 32
    q, k, v = _rand_qkv(1, B, S, Hq, Hkv, D)

    def loss_ref(q, k, v):
        return jnp.sum(_dot_attention(q, k, v) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=64,
                            block_k=128) ** 2)

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    g_fl = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4, err_msg=name)


def test_flash_noncausal_matches_softmax():
    B, S, H, D = 1, 128, 2, 32
    q, k, v = _rand_qkv(2, B, S, H, H, D)
    out = _jit(flash_attention, causal=False, block_q=64, block_k=128)(
        q, k, v)

    @jax.jit
    def softmax_attention(q, k, v):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (D ** -0.5)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    ref = softmax_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_bf16_close():
    B, S, Hq, Hkv, D = 2, 128, 4, 2, 64
    q, k, v = _rand_qkv(3, B, S, Hq, Hkv, D,
                        dtype=jnp.bfloat16)
    ref = jax.jit(_dot_attention)(q, k, v)
    out = _jit(flash_attention, causal=True, block_q=64, block_k=128)(
        q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("S,Hq,Hkv,D,block_q,block_k,strips", [
    (2048, 15, 5, 64, None, None, 4),    # smollm2's heads; 1024^2 tiles, one below
    (1024, 16, 8, 128, 512, 512, 2),     # internlm2's heads; 512^2 tiles
    (1024, 4, 2, 64, 256, 512, None),    # not square: whole tile and a select
    (1000, 4, 2, 64, None, None, 4),     # through the causal pad to 1024
])
def test_flash_strips_match_reference(S, Hq, Hkv, D, block_q, block_k,
                                      strips):
    """Forward and the three gradients where a tile the diagonal crosses
    is walked in several strips (and where it is not)."""
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    padded = S + -S % fa.LANES
    bq, bk = fa._block_sizes(padded, padded, block_q, block_k)
    strip = fa._diag_strip(bq, bk)
    assert (strip and bq // strip) == strips
    q, k, v = _rand_qkv(7, 1, S, Hq, Hkv, D)
    w = _normal(8, q.shape)
    ref = _dot_attention

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=block_q,
                               block_k=block_k)

    np.testing.assert_allclose(np.asarray(jax.jit(flash)(q, k, v)),
                               np.asarray(jax.jit(ref)(q, k, v)),
                               atol=2e-5, rtol=2e-5)
    g_ref = jax.jit(jax.grad(lambda *a: jnp.sum(ref(*a) * w),
                             argnums=(0, 1, 2)))(q, k, v)
    g_fl = jax.jit(jax.grad(lambda *a: jnp.sum(flash(*a) * w),
                            argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_fl, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("Hq,Hkv", [(4, 2), (2, 2)], ids=["gqa", "mha"])
@pytest.mark.parametrize("S", [256, 1024, 2048])
def test_flash_statistics_are_lane_dense_and_round_trip(S, Hq, Hkv):
    """The forward's ``lse`` is ``(B, H, 1, S)``, position ``p`` at
    ``[0, p]``, and is the log-sum-exp of the reference's causal scores
    there; handed on in that layout (``delta`` takes it), dq and dk/dv turn
    it back and give the reference's gradients."""
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    B, D = 1, 32
    q, k, v = (jnp.transpose(x, (0, 2, 1, 3)) for x in _rand_qkv(
        S + Hq, B, S, Hq, Hkv, D))
    do = _normal(11, q.shape)
    kw = dict(causal=True, block_q=None, block_k=None, interpret=True)
    assert fa._stats_dense(fa._block_sizes(S, S, None, None)[0])
    o_ref, lse_ref, g_ref = _reference_and_vjp(q, k, v, do, causal=True)
    o, lse = _jit(fa._fwd, **kw)(q, k, v)
    assert lse.shape == (B, Hq, 1, S) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(lse[:, :, 0, :]),
                               np.asarray(lse_ref), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    grads = _jit(fa._bwd_impl, out_dtype=jnp.float32, **kw)(
        q, k, v, o, lse, do)
    for a, b, name in zip(grads, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4, err_msg=name)


@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("Hq,Hkv", [(2, 2), (4, 2), (15, 5)],
                         ids=["group1", "group2", "group3"])
def test_flash_backward_does_its_own_gqa(Hq, Hkv, causal, out_dtype):
    """``_bwd_impl`` takes K/V at kv-head granularity and returns dq per q
    head, dk/dv per KV head (the group added in the kernel's float32
    accumulator), in the type its caller asks for: float32 (the ring's)
    is the float32 reference's gradient; bf16 (the model's) is that same
    float32 result rounded once.  Two q and two k blocks, so the walk over
    a group's heads crosses skipped, diagonal and whole tiles."""
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    B, S, D, block = 1, 256, 32, 128
    q, k, v = (jnp.transpose(x, (0, 2, 1, 3)) for x in _rand_qkv(
        Hq, B, S, Hq, Hkv, D))
    do = _normal(12, q.shape)
    kw = dict(causal=causal, block_q=block, block_k=block, interpret=True)
    o, lse = _jit(fa._fwd, **kw)(q, k, v)
    exact = _jit(fa._bwd_impl, out_dtype=jnp.float32, **kw)(
        q, k, v, o, lse, do)
    if out_dtype == jnp.float32:
        want = _reference_and_vjp(q, k, v, do, causal=causal)[2]
        grads, close = exact, 1e-4
    else:
        grads = _jit(fa._bwd_impl, out_dtype=out_dtype, **kw)(
            q, k, v, o, lse, do)
        want, close = [g.astype(out_dtype) for g in exact], 0
    assert [g.shape for g in grads] == [q.shape, k.shape, v.shape]
    for a, b, name in zip(grads, want, "qkv"):
        assert a.dtype == out_dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=close, rtol=close, err_msg=name)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize("Hq,Hkv", [(2, 2), (4, 2), (15, 5)],
                         ids=["group1", "group2", "group3"])
def test_flash_gqa_gradients_in_the_models_type(Hq, Hkv, causal):
    """``flash_attention``'s gradients on bf16 inputs arrive as bf16 at
    the model's granularity, (B, S, Hq | Hkv, D), and are the float32
    reference's on the same (rounded) inputs to bf16's precision."""
    B, S, D = 1, 256, 32
    q, k, v = _rand_qkv(Hkv, B, S, Hq, Hkv, D,
                        dtype=jnp.bfloat16)
    w = _normal(13, q.shape)

    def ref(q, k, v):
        out, _ = _gqa_reference(*(jnp.transpose(x, (0, 2, 1, 3)) for x in (
            q * D ** -0.5, k, v)), causal=causal)
        return jnp.transpose(out, (0, 2, 1, 3))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128)

    g_ref = jax.jit(jax.grad(lambda *a: jnp.sum(ref(*a) * w),
                             argnums=(0, 1, 2)))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    g_fl = jax.jit(jax.grad(lambda *a: jnp.sum(flash(*a) * w),
                            argnums=(0, 1, 2)))(q, k, v)
    for a, b, x, name in zip(g_fl, g_ref, (q, k, v), "qkv"):
        assert a.dtype == jnp.bfloat16 and a.shape == x.shape
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b),
                                   atol=4e-2, rtol=4e-2, err_msg=name)


@pytest.mark.parametrize("Sq,block_q", [(64, None), (256, 32), (192, 64)])
def test_flash_statistics_keep_the_column_under_a_lane_of_rows(Sq, block_q):
    """A q block that is not whole lanes keeps the width-1 column, and
    both layouts hold a row's statistic at the same row-major place (what
    the ring's merge leans on)."""
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    q, k, v = (jnp.transpose(x, (0, 2, 1, 3)) for x in _rand_qkv(
        Sq, 1, Sq, 2, 2, 32))
    kw = dict(causal=True, block_k=None, interpret=True)
    o, lse = _jit(fa._fwd, block_q=block_q, **kw)(q, k, v)
    assert lse.shape == (1, 2, Sq, 1)
    if Sq % 128 == 0:
        _, dense = _jit(fa._fwd, block_q=128, **kw)(q, k, v)
        assert dense.shape == (1, 2, 1, Sq)
        np.testing.assert_allclose(np.asarray(dense).reshape(lse.shape),
                                   np.asarray(lse), atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("by", ["cols", "rows"])
def test_diag_strips_cover_the_causal_half_once(by):
    """No masked element contributes, no unmasked element is skipped: the
    strips of a diagonal tile cover every element on or below the
    diagonal exactly once, what they hold above it lies in their corner
    ON the diagonal, and ``causal_computed_share`` is the covered area."""
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    block, strip = 1024, 256
    strips = fa._diag_strips(block, strip, by)
    assert len(strips) == block // strip
    seen = np.zeros((block, block), np.int32)
    for (r0, r1), (c0, c1) in strips:
        rows = np.arange(r0, r1)[:, None]
        cols = np.arange(c0, c1)[None, :]
        above = cols > rows
        lo = max(r0, c0)              # the corner: [lo, lo + strip) squared
        assert not above[rows[:, 0] >= lo + strip].any()
        assert not above[:, cols[0] < lo].any()
        # what _causal_mask(s, r0, c0) keeps of the strip
        seen[r0:r1, c0:c1] += ~above
    np.testing.assert_array_equal(seen, np.tri(block, dtype=np.int32))
    area = sum((r1 - r0) * (c1 - c0) for (r0, r1), (c0, c1) in strips)
    assert area / block ** 2 == (1 + strip / block) / 2 == 0.625
    # 2,048: four tiles, one skipped, one whole, two in strips
    assert fa.causal_computed_share(2048, 1024, 1024, 256) == \
        (block ** 2 + 2 * area) / 2048 ** 2 == 0.5625
    assert fa.causal_computed_share(2048, 1024, 1024, 1024) == 0.75
    assert fa.causal_computed_share(4096, 1024, 1024, 256) == 0.53125
    assert fa.causal_computed_share(4096, 1024, 1024, 1024) == 0.625
    assert fa.causal_computed_share(2048) == fa.causal_computed_share(
        2048, strip=fa.DIAG_STRIP)
    assert fa.causal_computed_share(1024, 256, 512) == 0.75   # not square


@pytest.mark.parametrize("seq_shards", [2, 4])
def test_ring_forward_matches_reference(seq_shards):
    B, S, Hq, Hkv, D = 2, 256, 4, 2, 32
    q, k, v = _rand_qkv(4, B, S, Hq, Hkv, D)
    ref = jax.jit(_dot_attention)(q, k, v)
    mesh = MeshSpec(seq=seq_shards).build(jax.devices()[:seq_shards])
    with use_mesh(mesh):
        out = jax.jit(functools.partial(ring_attention, mesh=mesh))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_backward_matches_reference():
    B, S, Hq, Hkv, D = 1, 256, 4, 2, 32
    q, k, v = _rand_qkv(5, B, S, Hq, Hkv, D)

    def loss_ref(q, k, v):
        return jnp.sum(_dot_attention(q, k, v) ** 2)

    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)

    mesh = MeshSpec(seq=4).build(jax.devices()[:4])
    with use_mesh(mesh):
        def loss_ring(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh=mesh) ** 2)

        g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b, name in zip(g_ring, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4, err_msg=name)


def test_flash_fallback_small_shapes():
    # Debug-model shapes (S=32, D=16) take the einsum fallback on TPU
    # and interpret mode on CPU; either way numerics match.
    B, S, H, D = 2, 32, 4, 16
    q, k, v = _rand_qkv(6, B, S, H, H, D)
    ref = jax.jit(_dot_attention)(q, k, v)
    out = _jit(flash_attention, causal=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_packed_positions_rejected_on_flash():
    from ray_tpu.models.llama import LlamaConfig, forward, init_params
    cfg = LlamaConfig.debug(attention_impl="flash")
    params = init_params(jax.random.key(0), cfg)
    toks = jnp.zeros((1, 32), jnp.int32)
    pos = jnp.concatenate([jnp.arange(16), jnp.arange(16)])[None, :]
    with pytest.raises(NotImplementedError):
        forward(params, toks, cfg, positions=pos.astype(jnp.int32))

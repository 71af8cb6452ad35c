"""``ops/index_select.py``: a prefill's selection of keys as one kernel
(interpret mode on the CPU) against XLA's form, ``indexer.scores`` +
``indexer.topk_keep``, on scores that both compute exactly."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import indexer
from ray_tpu.ops import index_select

flash = importlib.import_module("ray_tpu.ops.flash_attention")
TILE = indexer.QUERY_TILE
HEADS, HEAD_DIM = 4, 64


def _operands(seed, P, plateaus=True):
    """``qI, kI_t, w`` of one row in small whole numbers: every order of
    summation gives the same float32 score, so the two forms' masks are
    held equal and not merely close.  Relu and weights of both signs leave
    zeros of both signs and plateaus at every height; ``plateaus`` adds
    runs of ONE key (a padded prompt's), rows that weigh nothing and a
    query no key answers."""
    rng = np.random.default_rng(seed)
    qi = rng.integers(-2, 3, (1, P, HEADS, HEAD_DIM)).astype(np.float32)
    ki = rng.integers(-1, 2, (1, HEAD_DIM, P)).astype(np.float32)
    w = rng.integers(-2, 3, (1, P, HEADS)).astype(np.float32)
    if plateaus:
        ki[0, :, 40:300] = ki[0, :, 40:41]        # a plateau in every row
        ki[0, :, P - 200:] = ki[0, :, P - 1:]     # the padding's one token
        w[0, 5::7] = 0.0                          # every score +-0.0
        w[0, 6::7] = -np.abs(w[0, 6::7])          # scores <= 0, many -0.0
        qi[0, 9::11] = 0.0
    return (jnp.asarray(qi, jnp.bfloat16), jnp.asarray(ki, jnp.bfloat16),
            jnp.asarray(w))


def _xla(qi, ki_t, w, k):
    P = qi.shape[1]
    at = jnp.arange(P, dtype=jnp.int32)
    score = jnp.where(at[None, :] <= at[:, None],
                      indexer.scores(qi, ki_t, w), -jnp.inf)
    return np.asarray(indexer.topk_keep(score, k))


@pytest.mark.parametrize("tiles,k,length", [
    (2, 200, 1024),         # k under a tile, a full row
    (2, 700, 1024),         # k over a tile
    (3, 200, 1536),
    (3, 1100, 1536),        # the first two tiles have fewer candidates
    (3, 200, 900),          # an end inside a tile, a tile wholly past it
    (3, 700, 512),          # two tiles wholly past the end
    (2, 200, 1),            # one real query
    (2, 1023, 1024),        # every row but the last has fewer than k
])
def test_the_mask_is_topk_keeps(tiles, k, length):
    P = tiles * TILE
    qi, ki_t, w = _operands(tiles * 1000 + k, P)
    assert index_select.engages(1, P, k, HEADS, HEAD_DIM, TILE)
    want = _xla(qi, ki_t, w, k)
    got = np.asarray(indexer.prefill_keep(
        qi, ki_t, w, k, jnp.asarray([length], jnp.int32)))
    assert got.shape == (1, P, P) and got.dtype == np.int8
    # the rows it selects for: whole blocks of ROWS that start before the end
    run = min(-(-length // index_select.ROWS) * index_select.ROWS, P)
    # equal everywhere the forward reads for a real query, and in the rest
    # of a block that runs wherever no tie is broken
    assert ((got[0, :length] != 0) == want[0, :length]).all()
    assert not got[0, run:].any()
    kept = (got[0, :run] != 0).sum(-1)
    assert (kept[:length] == np.minimum(np.arange(length) + 1, k)).all()
    assert (kept >= np.minimum(np.arange(run) + 1, k)).all()
    assert not np.triu(got[0], 1).any()


def test_ties_go_to_the_lower_positions_and_short_rows_keep_everything():
    """``test_exact_topk_with_ties``' cases through the kernel: a plateau
    at the k-th place, plateaus of zeros (what relu leaves, under weights
    of both signs), rows of fewer candidates than k -- against a stable
    sort, not against the form it replaces."""
    P, k = 2 * TILE, 100
    qi, ki_t, w = _operands(3, P)
    score = np.asarray(indexer.scores(qi, ki_t, w))[0]
    assert (score == 0).sum() > P
    got = np.asarray(indexer.prefill_keep(qi, ki_t, w, k)) != 0
    tied = 0
    for t in range(P):
        order = np.argsort(-score[t, :t + 1], kind="stable")[:k]
        assert sorted(np.flatnonzero(got[0, t])) == sorted(order), t
        kth = score[t, order[-1]]
        tied += (score[t, :t + 1] == kth).sum() > (got[0, t]
                                                   & (score[t] == kth)).sum()
    assert tied > P // 2        # most rows break a tie at the k-th place


def test_without_lengths_every_row_is_selected_for():
    P, k = 2 * TILE, 300
    qi, ki_t, w = _operands(5, P, plateaus=False)
    got = np.asarray(indexer.prefill_keep(qi, ki_t, w, k))
    assert ((got != 0) == _xla(qi, ki_t, w, k)).all()


@pytest.mark.parametrize("rows,keys,k,heads,head_dim,why", [
    (2, 1024, 200, 16, 64, "several rows a launch"),
    (4, 4096, 2048, 16, 64, "several rows a launch"),
    (1, 1024, 1024, 16, 64, "a prompt no longer than k"),
    (1, 512, 2048, 16, 64, "a prompt no longer than k"),
    (1, 1024, 200, 4, 8, "a toy width"),
    (1, 1000, 200, 16, 64, "no whole tiles"),
    (1, 32768, 2048, 16, 64, "more keys than its VMEM holds"),
])
def test_other_shapes_keep_xlas_form(rows, keys, k, heads, head_dim, why):
    assert not index_select.engages(rows, keys, k, heads, head_dim, TILE), why


def test_cell_10s_shapes_engage():
    for bucket in (4096, 8192, 12288):
        assert index_select.engages(1, bucket, 2048, 16, 64, TILE)


def test_a_shape_that_does_not_engage_never_calls_the_kernel(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(index_select, "prefill_keep", refuse)
    rng = np.random.default_rng(0)
    qi = jnp.asarray(rng.normal(size=(2, 64, 4, 8)), jnp.float32)
    ki_t = jnp.asarray(rng.normal(size=(2, 8, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(2, 64, 4)), jnp.float32)
    lengths = jnp.asarray([64, 10], jnp.int32)
    told = indexer.prefill_keep(qi, ki_t, w, 16, lengths)
    assert (np.asarray(told) == np.asarray(
        indexer.prefill_keep(qi, ki_t, w, 16))).all()
    assert indexer.prefill_keep(qi, ki_t, w, 64, lengths) is None


@pytest.mark.parametrize("length", [1536, 1100, 1024, 900, 130])
def test_the_masked_forward_is_finite_and_the_shipped_pairs(length):
    """``flash_prefill_attention(keep=the kernel's, lengths=...)``: finite
    everywhere -- its q block of 1,024 rows straddles the row's end and
    reads rows of zeros there -- and, at every position before the length,
    what XLA's mask gives."""
    P, k, Hq, Hkv, D = 3 * TILE, 200, 4, 2, 64
    qi, ki_t, w = _operands(length, P)
    ks = jax.random.split(jax.random.key(length), 3)
    q = jax.random.normal(ks[0], (1, P, Hq, D), jnp.float32)
    key = jax.random.normal(ks[1], (1, P, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (1, P, Hkv, D), jnp.float32)
    lengths = jnp.asarray([length], jnp.int32)

    def attend(keep):
        return np.asarray(flash.flash_prefill_attention(
            q, key, v, scale=D ** -0.5, keep=keep, lengths=lengths,
            lse=False))

    got = attend(indexer.prefill_keep(qi, ki_t, w, k, lengths))
    want = attend(jnp.asarray(_xla(qi, ki_t, w, k), jnp.int8))
    assert np.isfinite(got).all()
    assert (got[0, :length] == want[0, :length]).all()

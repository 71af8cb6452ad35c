"""The Mamba-1 prefill kernel (``ray_tpu/ops/mamba1_scan.py``, interpreted
here) against the recurrence as it is written, position after position in
float32 -- ``models/mamba1.selective_scan``, the loop that channels which
are no whole blocks keep."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import mamba1
from ray_tpu.ops import mamba1_scan as op


selective_scan = functools.partial(mamba1.selective_scan, chunk=1)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _inputs(seed, G, P, Di, N, lengths):
    """As a layer hands them over: ``A_log`` and ``dt_bias`` drawn as
    ``mamba1.init_params`` draws them, ``dt`` a softplus and 0 at a row's
    padded positions."""
    ks = jax.random.split(jax.random.key(seed), 7)
    A = -jax.random.uniform(ks[0], (N, Di), minval=1.0, maxval=16.0)
    rate = jnp.exp(jax.random.uniform(
        ks[1], (Di,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (G, P, Di))
                         + rate + jnp.log(-jnp.expm1(-rate)))
    live = jnp.arange(P)[None, :] < lengths[:, None]
    dt = jnp.where(live[..., None], dt, 0.0)
    u = jax.nn.silu(2.0 * jax.random.normal(ks[3], (G, P, Di)))
    B, C = (jax.random.normal(k, (G, P, N)) for k in ks[4:6])
    D = 1.0 + 0.1 * jax.random.normal(ks[6], (Di,))
    return u, dt, A, B, C, D, live


# (positions a block, P, Di, N, the rows' lengths)
CASES = {
    # three blocks of 16, the last one ragged; rows that end inside a
    # block, inside a group of 8, at 1
    "rows_of_different_lengths": (16, 40, 1024, 16, (40, 13, 1)),
    # a row that ends on a block's edge and one on a group's
    "lengths_on_the_edges": (16, 48, 1024, 4, (32, 8, 48)),
    # two blocks of channels, a bucket that is no whole group of 8
    "two_blocks_of_channels": (16, 13, 2048, 4, (13, 5)),
    # the block as shipped: a prompt longer than one, not a whole one
    "the_shipped_block": (None, 300, 1024, 4, (300, 257)),
}


@pytest.fixture(scope="module", params=list(CASES))
def case(request):
    block, P, Di, N, lengths = CASES[request.param]
    shipped, rule = op.POSITIONS, op.engages
    # (a group of several rows keeps the loop where it is served: the
    # kernel itself takes any)
    op.POSITIONS = block or shipped
    op.engages = lambda channels, rows: rule(channels, 1)
    try:
        lengths = jnp.asarray(lengths, jnp.int32)
        u, dt, A, B, C, D, live = _inputs(P + Di, len(lengths), P, Di, N,
                                          lengths)
        assert op.engages(Di, len(lengths))
        got = op.mamba1_scan(u, dt, A, B, C, D, lengths, 1)
        covered = op.padded_len(P)
    finally:
        op.POSITIONS, op.engages = shipped, rule
    y, S = jax.jit(selective_scan)(u, dt, A, B, C)
    return (u, dt, A, B, C, D, np.asarray(lengths), np.asarray(live),
            block or shipped, covered), got, (y + D * u, S)


def test_y_at_real_positions_is_the_recurrences(case):
    (*_, live, _, _), (y, _), (want, _) = case
    assert y.shape == want.shape and y.dtype == jnp.float32
    assert float(jnp.abs(want).max()) > 1.0
    np.testing.assert_allclose(np.where(live[..., None], y, 0.0),
                               np.where(live[..., None], want, 0.0),
                               atol=2e-5, rtol=1e-5)


def test_the_state_is_that_of_each_rows_last_real_position(case):
    """The reference walks the padding too (``dt`` = 0 there), and on a
    row cut at its length: a padded position changes nothing."""
    (u, dt, A, B, C, _, lengths, *_), (_, S), (_, want) = case
    assert S.shape == want.shape and S.dtype == jnp.float32
    np.testing.assert_allclose(S, want, atol=1e-5, rtol=1e-5)
    for row, n in enumerate(lengths):
        _, cut = selective_scan(*(a[row:row + 1, :n] for a in (u, dt)), A,
                                *(a[row:row + 1, :n] for a in (B, C)))
        np.testing.assert_allclose(S[row], cut[0], atol=1e-5, rtol=1e-5)


def test_y_past_the_last_group_walked_is_zero(case):
    (*_, lengths, _, block, covered), (y, _), _ = case
    y = np.asarray(y)
    assert np.isfinite(y).all()
    assert covered % min(block, covered) == 0 and covered >= y.shape[1]
    for row, n in enumerate(lengths):
        walked = -(-int(n) // 8) * 8
        assert not y[row, walked:].any()
        assert y[row, :n].any()


def test_channels_that_are_no_whole_block_keep_the_loop():
    """By shape, as ``kda_chunk.engages``: the toy presets' 128 channels,
    and what the loop leaves past a row's length is its own (not zeros)."""
    assert op.engages(5120, 1) and op.engages(1024, 1)
    assert not op.engages(128, 1) and not op.engages(1100, 1)
    # a group of several rows too (PERF.md section 6 (g), PR 62)
    assert not op.engages(5120, 2) and not op.engages(5120, 4)
    lengths = jnp.asarray([24, 9], jnp.int32)
    u, dt, A, B, C, D, live = _inputs(3, 2, 24, 200, 16, lengths)
    y, S = op.mamba1_scan(u, dt, A, B, C, D, lengths, 8)
    want, state = mamba1.selective_scan(u, dt, A, B, C, 8)
    np.testing.assert_array_equal(y, want + D * u)
    np.testing.assert_array_equal(S, state)
    assert np.asarray(y)[1, 16:].any()


def test_a_bucket_is_covered_in_whole_blocks():
    assert op.CHANNELS == 1024
    block = op.POSITIONS
    assert block % 8 == 0
    assert [op.padded_len(P) for P in (1, 8, 13, block, block + 1,
                                       4096, 12288)] \
        == [8, 8, 16, block, 2 * block, 4096, 12288]


def test_the_kernel_is_imported_where_a_prefill_asks_for_it():
    """Another model's start does not pay for this one: neither module is
    loaded by the modules every engine imports, and the kernel's only where
    ``mamba1.prefill`` asks for it."""
    import os
    import subprocess
    import sys

    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, ray_tpu.serve.llm, ray_tpu.models.llama_serve;"
         "print([m for m in sys.modules if 'mamba1' in m]);"
         "import ray_tpu.models.mamba1;"
         "print([m for m in sys.modules if 'mamba1' in m])"],
        capture_output=True, text=True, check=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.split("\n")[:2] == ["[]", "['ray_tpu.models.mamba1']"], \
        out.stdout

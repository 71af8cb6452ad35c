"""One decode step of a KDA layer's recurrence for TPU (Pallas): a gated
delta rule on a matrix a head, every advancing slot's state read once and
written once, where it lies, and a slot that does not advance neither read
nor written.

    S' = a (.) S;  r = k^T S';  S'' = S' + b k (v - r)^T;  o = S''^T q

per advancing slot and head, ``S`` ``(d, d)`` with the keys' channels on
the sublanes and the values' on the lanes, ``a = exp(g)`` a key channel.

The states of all layers and slots are ONE array, ``(Lk, B, H, d, d)``
float32: the carry of the serving loops (``llama_serve.decode_step``), this
kernel's operand, aliased to its result.  On ``ops/ssm_state_update.py``'s
plan (``_plan``):

- the grid walks blocks of ``_HEADS`` heads and, inside, the slots; a
  slot's block of heads is fetched and written back by the pipeline
  (double buffered) at the layer a scalar-prefetch operand names;
- a slot that is not active maps to the block of the last active slot
  before it, so the pipeline moves nothing for it; ahead of the first
  active slot the block is that slot's, copied through unchanged;
- ``a``, ``k`` and ``q`` scale the state's ROWS, so each is wanted down
  the sublanes: a block's ``(heads, d)`` tile of each is padded to ``(d,
  d)`` and transposed once, and a head's column is broadcast along the
  lanes; ``r`` and ``o`` are sums down the sublanes and lie along the
  lanes, as ``v`` does and the next op wants them.  All of it on the
  vector unit in float32: the matrix unit would round the state.

A state Mosaic cannot tile (``d`` not whole 128-lane tiles: the toy
presets) is updated by XLA, by shape (``_xla_update``: the same
arithmetic), on the chip and off it; interpret mode runs the kernel on the
CPU for the test suite, decided as ``ops/decode_attention.py`` decides.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_flash = importlib.import_module("ray_tpu.ops.flash_attention")
_plan = importlib.import_module("ray_tpu.ops.ssm_state_update")._plan
LANES = _flash.LANES
# Heads of a slot the kernel holds at a time: 2 MiB of state at d = 128,
# twice (double buffered) in and out.
_HEADS = 32
_VMEM_LIMIT = 48 << 20


def _heads_a_block(heads: int, d: int) -> int:
    return max(n for n in range(1, min(_HEADS, d, heads) + 1)
               if heads % n == 0)


def _kernel(layer_ref, block_ref, mode_ref, s_ref, akq_ref, vb_ref, o_ref,
            y_ref, *, heads, d):
    mode = mode_ref[pl.program_id(1)]

    @pl.when(mode == 1)
    def _update():
        f32 = jnp.float32
        pad = jnp.zeros((d - heads, d), f32)

        def columns(i):                # head h's vector down column h
            return jnp.concatenate([akq_ref[0, i], pad], 0).T \
                if heads < d else akq_ref[0, i].T

        a_t, k_t, q_t = columns(0), columns(1), columns(2)
        for h in range(heads):
            at = slice(h, h + 1)
            k = k_t[:, at]
            s = a_t[:, at] * s_ref[0, 0, h].astype(f32)
            r = jnp.sum(k * s, axis=0, keepdims=True)
            s = s + k * (vb_ref[0, 0, at] - vb_ref[0, 1, at] * r)
            o_ref[0, 0, h] = s.astype(o_ref.dtype)
            y_ref[0, at] = jnp.sum(q_t[:, at] * s, axis=0, keepdims=True)

    @pl.when(mode == 2)
    def _through():
        o_ref[...] = s_ref[...]

    @pl.when(mode != 1)
    def _no_output():
        y_ref[...] = jnp.zeros_like(y_ref)


def _xla_update(ssm, layer, active, decay, q, k, v, b):
    f32 = jnp.float32
    held = jax.lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)
    s = decay[..., None] * held.astype(f32)
    r = jnp.sum(k[..., None] * s, axis=-2)
    s = s + k[..., None] * (b[..., None] * (v - r))[..., None, :]
    new = jnp.where(active[:, None, None, None], s.astype(ssm.dtype), held)
    o = jnp.sum(q[..., None] * s, axis=-2)
    return (jax.lax.dynamic_update_index_in_dim(ssm, new, layer, 0),
            jnp.where(active[:, None, None], o, 0.0))


def kda_state_update(ssm: jax.Array, layer: jax.Array, active: jax.Array,
                     decay: jax.Array, q: jax.Array, k: jax.Array,
                     v: jax.Array, b: jax.Array):
    """ssm (Lk, B, H, d, d) the stacked states; layer () int32; active (B,)
    bool; decay, q, k, v (B, H, d) float32, ``decay = exp(g)`` a key
    channel, q scaled and q, k normed as the layer does; b (B, H) float32.
    Returns (ssm with layer ``layer`` of the active slots advanced, o (B,
    H, d) float32 = the new state's ``S^T q``; 0 for a slot that is not
    active).  The output contracts the state as computed, float32 (the
    state is stored float32: ``ssm_state_dtype`` of a KDA config)."""
    _lm, slots, heads, d, _ = ssm.shape
    if d % LANES:
        return _xla_update(ssm, layer, active, decay, q, k, v, b)
    f32 = jnp.float32
    hb = _heads_a_block(heads, d)
    block, mode = _plan(active)
    akq = jnp.stack([decay, k, q], 1).astype(f32)           # (B, 3, H, d)
    vb = jnp.stack([b[..., None] * v,
                    jnp.broadcast_to(b[..., None], v.shape)], 1).astype(f32)

    def state_at(g, r, layer, block, mode):
        return (layer[0], block[r], g, 0, 0)

    def vectors_at(g, r, layer, block, mode):
        return (block[r], 0, g, 0)

    state = pl.BlockSpec((1, 1, hb, d, d), state_at)
    out, o = pl.pallas_call(
        functools.partial(_kernel, heads=hb, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(heads // hb, slots),
            in_specs=[state,
                      pl.BlockSpec((1, 3, hb, d), vectors_at),
                      pl.BlockSpec((1, 2, hb, d), vectors_at)],
            out_specs=[state,
                       pl.BlockSpec((1, hb, d),
                                    lambda g, r, *_: (r, g, 0))]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((slots, heads, d), f32)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_flash._use_interpret(), name="kda_state_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), block, mode, ssm, akq, vb)
    return out, o

"""One decode step of a Mamba-2 layer's recurrence for TPU (Pallas): every
slot's state read once and written once, where it lies, and a slot that
does not advance neither read nor written.

    S <- decay * S + B (x) dtx        y = S C         per advancing slot

(B and C come in ``R`` groups of ``N`` state dimensions: the channels are
``R`` equal runs of lanes, and run ``g`` is fed by ``B_g`` and read by
``C_g``.  One group -- Granite 4 -- is every channel under the one B and C.)

The states of all layers and slots are ONE array, ``(Lm, B, N, HD)``:
the carry of the serving loops (``llama_serve.decode_step``).  Written in
XLA, a layer's update is two fusions -- one contracts the new state with
C, one writes it into the stack -- that each read the layer's
``(B, N, HD)`` slice, for every slot, active or not: three passes over
4.5 GB a step at granite-4.0-h-micro's widths where the live slots' one
read and one write are 7.2 (PERF.md section 6, PR 30: 23 ms of a 40 ms
step at 44% of that floor).  This kernel's operand is the whole stack,
aliased to its result:

- the grid walks the slots; a slot's ``(N, HD)`` state is one block,
  fetched and written back by the pipeline (double buffered) at the
  layer a scalar-prefetch operand names;
- a slot that is not active maps to the block of the last active slot
  before it, so the pipeline neither fetches nor writes anything for it
  (a block index that does not change moves nothing); ahead of the first
  active slot the block is that slot's, copied through unchanged
  (``_plan``);
- the state's layout keeps the ``HD = heads x head_dim`` channels on the
  lanes and the ``N`` state dimensions on the sublanes, so the decay and
  ``dtx`` are rows broadcast down the sublanes, a group's ``B`` is a
  column made by one tile transpose, and ``y = C S`` is a matmul whose
  result lies along the lanes as the next op wants it; the lanes are
  computed ``_CHUNK`` at a time, a chunk under ITS group's column and row
  (a group is a whole number of chunks: 8,192 lanes in 8 groups are two
  chunks of 512 a group).

Arithmetic in float32; the state is rounded to its storage type once,
and ``y`` contracts the state AS STORED (what the next step will read).

A state Mosaic cannot tile (``N`` or a group's ``HD / R`` lanes not whole
128-lane tiles: the toy presets) is updated by XLA, by shape (``_xla_update``: the same
arithmetic); interpret mode runs the kernel on the CPU for the test
suite, decided as ``ops/decode_attention.py`` decides.
"""

from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_flash = importlib.import_module("ray_tpu.ops.flash_attention")
LANES = _flash.LANES
# Lanes of a state the kernel computes at a time: (N, 512) float32
# temporaries are 256 KiB each beside the 2 x 2 blocks of 1 MiB.
_CHUNK = 512
# Mosaic's scoped VMEM unless a call asks for more: a state of 128 x 8,192
# float32 is a block of 4 MiB, and the pipeline holds four.
_SCOPED_VMEM = 16 << 20


def _tiles(n: int, hd: int, groups: int = 1) -> bool:
    return n % LANES == 0 and (hd // groups) % LANES == 0


def _plan(active: jax.Array):
    """Per grid step (slot): (the slot whose block the step holds, what
    it does: 1 update, 0 nothing -- the block is that of an active slot
    before it, already updated --, 2 copy the block through unchanged)."""
    slots = active.shape[0]
    rows = jnp.arange(slots, dtype=jnp.int32)
    last = jax.lax.cummax(jnp.where(active, rows, -1))
    first = jnp.min(jnp.where(active, rows, slots))
    block = jnp.where(last >= 0, last, jnp.where(first < slots, first, 0))
    mode = jnp.where(active, 1, jnp.where(last >= 0, 0, 2))
    return block.astype(jnp.int32), mode.astype(jnp.int32)


def _kernel(layer_ref, block_ref, mode_ref, s_ref, decay_ref, dtx_ref,
            b_ref, c_ref, o_ref, y_ref, *, chunk, precision):
    mode = mode_ref[pl.program_id(0)]
    n, hd = s_ref.shape[2], s_ref.shape[3]

    @pl.when(mode == 1)
    def _update():
        f32 = jnp.float32
        per_group = hd // chunk // b_ref.shape[1]     # chunks a group
        for j in range(hd // chunk):
            if j % per_group == 0:
                # the group's B down the sublanes: a lanes-constant tile
                # by one transpose
                g = slice(j // per_group, j // per_group + 1)
                b_col = jnp.broadcast_to(b_ref[0, g], (n, n)).T[:, :1]
                c_rows = jnp.broadcast_to(c_ref[0, g], (8, n)).astype(
                    o_ref.dtype)
            at = pl.ds(j * chunk, chunk)
            new = (decay_ref[0, :, at] * s_ref[0, 0, :, at].astype(f32)
                   + b_col * dtx_ref[0, :, at])
            stored = new.astype(o_ref.dtype)
            o_ref[0, 0, :, at] = stored
            y_ref[0, :, at] = jax.lax.dot_general(
                c_rows, stored, (((1,), (0,)), ((), ())),
                preferred_element_type=f32, precision=precision)[:1]

    @pl.when(mode == 2)
    def _through():
        o_ref[...] = s_ref[...]

    @pl.when(mode != 1)
    def _no_output():
        y_ref[...] = jnp.zeros_like(y_ref)


def _xla_update(ssm, layer, active, decay, dtx, b, c):
    s = jax.lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)
    slots, n, hd = s.shape
    groups = b.shape[1]
    by_group = (slots, n, groups, hd // groups)
    new = (decay[:, None, :] * s.astype(jnp.float32)
           + (b.transpose(0, 2, 1)[..., None]
              * dtx.reshape(slots, 1, groups, -1)).reshape(s.shape)
           ).astype(ssm.dtype)
    new = jnp.where(active[:, None, None], new, s)
    y = jnp.einsum("bngj,bgn->bgj", new.astype(jnp.float32).reshape(by_group),
                   c).reshape(slots, hd)
    return (jax.lax.dynamic_update_index_in_dim(ssm, new, layer, 0),
            jnp.where(active[:, None], y, 0.0))


def ssm_state_update(ssm: jax.Array, layer: jax.Array, active: jax.Array,
                     decay: jax.Array, dtx: jax.Array, b: jax.Array,
                     c: jax.Array):
    """ssm (Lm, B, N, HD) the stacked states; layer () int32; active (B,)
    bool; decay (B, HD) float32, each head's ``exp(dt A)`` over its
    channels; dtx (B, HD) float32, ``dt * x``; b, c (B, R, N) float32, a
    row a group of ``HD / R`` channels.
    Returns (ssm with layer ``layer`` of the active slots advanced, y
    (B, HD) float32 = the new state contracted with c; 0 for a slot that
    is not active)."""
    _lm, slots, n, hd = ssm.shape
    groups = b.shape[1]
    interpret = _flash._use_interpret()
    if not interpret and not _tiles(n, hd, groups):
        return _xla_update(ssm, layer, active, decay, dtx, b, c)
    chunk = min(_CHUNK, hd // groups)
    block, mode = _plan(active)

    def row(x):
        return x.astype(jnp.float32)[:, None, :]

    def vector(width, rows=1):
        return pl.BlockSpec((1, rows, width), lambda r, *_: (r, 0, 0))

    state = pl.BlockSpec((1, 1, n, hd),
                         lambda r, layer, block, mode: (layer[0], block[r],
                                                        0, 0))
    precision = (jax.lax.Precision.HIGHEST if ssm.dtype == jnp.float32
                 else None)
    # in and out, double buffered, and the chunk's temporaries
    vmem = 4 * n * hd * ssm.dtype.itemsize + (4 << 20)
    out, y = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(slots,),
            in_specs=[state, vector(hd), vector(hd), vector(n, groups),
                      vector(n, groups)],
            out_specs=[state, vector(hd)]),
        out_shape=[jax.ShapeDtypeStruct(ssm.shape, ssm.dtype),
                   jax.ShapeDtypeStruct((slots, 1, hd), jnp.float32)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=vmem if vmem > _SCOPED_VMEM else None),
        interpret=interpret, name="ssm_state_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), block, mode, ssm,
      row(decay), row(dtx), b.astype(jnp.float32), c.astype(jnp.float32))
    return out, y[:, 0]

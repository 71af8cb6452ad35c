"""Pipeline parallelism: GPipe microbatch schedule over the ``pipe``
mesh axis.

Reference: the reference ships NO pipeline training schedule — its
compiled-graph substrate (dag/dag_node_operation.py:506-539 overlap
schedules, NCCL p2p channels) is the intended building block and the
TPU build must supply the strategy natively (SURVEY §2.3).

TPU-first design: the schedule is a single jitted program, not an
actor choreography.  Each pipe rank holds a contiguous slice of the
stacked layer weights (the existing ("layers", "pipe") sharding rule);
``shard_map`` runs the per-stage code; activations move stage→stage
with ``lax.ppermute`` over the ICI ring; the tick loop is a
``lax.scan``.  Differentiating through it yields the reverse pipeline
automatically (ppermute transposes to the reverse ring) — GPipe
semantics: all-forward then all-backward per microbatch set, bubble
fraction (P-1)/(M+P-1) each way.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

PyTree = Any


def bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """Idle fraction of the GPipe schedule (per direction)."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)


def pipeline_layers(layer_fn: Callable[[jax.Array, PyTree], jax.Array],
                    stacked_params: PyTree, x: jax.Array, *,
                    mesh: Mesh, num_microbatches: int,
                    pipe_axis: str = "pipe",
                    batch_axes=()) -> jax.Array:
    """Apply L stacked layers to ``x`` (B, S, E), layer-sharded into
    P = mesh.shape[pipe_axis] stages with an M-microbatch GPipe
    schedule.  ``layer_fn(h, layer_slice) -> h`` applies ONE layer (any
    remat wrapping included).  ``batch_axes``: mesh axes the microbatch
    batch dim is sharded over (data parallel composes with pp).

    The whole mesh is manualized (a partial-manual variant that leaves
    fsdp/tensor compiler-managed inside stages hangs XLA:CPU compiles
    as of jax 0.9); a stage therefore holds its L/P layers gathered —
    fine at the scales pipe stages target today, revisit for
    fsdp-inside-pp at 8B+."""
    n_pipe = mesh.shape[pipe_axis]
    M = num_microbatches
    B = x.shape[0]
    if B % M:
        raise ValueError(f"batch {B} not divisible by microbatches {M}")
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if L % n_pipe:
        raise ValueError(f"{L} layers not divisible by pipe={n_pipe}")
    x_mb = x.reshape(M, B // M, *x.shape[1:])

    batch_spec = tuple(batch_axes) if batch_axes else None
    x_spec = P(None, batch_spec, *(None,) * (x.ndim - 1))
    param_spec = jax.tree.map(lambda _: P(pipe_axis), stacked_params)

    from .sharding import suppress_constraints

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(param_spec, x_spec), out_specs=x_spec,
        check_vma=False)
    def run(local_layers, xmb):
        idx = jax.lax.axis_index(pipe_axis)
        T = M + n_pipe - 1

        def apply_local(h):
            def body(h, layer):
                # Global sharding constraints don't apply inside the
                # fully-manual region; the shard_map specs own layout.
                with suppress_constraints():
                    return layer_fn(h, layer), None

            h, _ = jax.lax.scan(body, h, local_layers)
            return h

        perm = [(i, (i + 1) % n_pipe) for i in range(n_pipe)]

        def tick(carry, t):
            prev_out, outputs = carry
            recv = jax.lax.ppermute(prev_out, pipe_axis, perm)
            x_t = xmb[jnp.clip(t, 0, M - 1)]
            # Stage 0 feeds from the microbatch stream; later stages
            # from their predecessor's previous-tick output.
            inp = jnp.where(idx == 0, x_t, recv)
            out = apply_local(inp)
            # The last stage emits microbatch t-(P-1) at tick t.
            store = jnp.clip(t - (n_pipe - 1), 0, M - 1)
            valid = (t >= n_pipe - 1).astype(out.dtype)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                outputs[store] * (1 - valid) + out * valid,
                store, 0)
            return (out, outputs), None

        outputs0 = jnp.zeros_like(xmb)
        carry0 = (jnp.zeros_like(xmb[0]), outputs0)
        (last, outputs), _ = jax.lax.scan(
            tick, carry0, jnp.arange(T, dtype=jnp.int32))
        # Every rank stored its own stage outputs; only the last
        # stage's are the pipeline's. Zero the rest and share over the
        # pipe ring so downstream (head/loss) stays replicated.
        outputs = jnp.where(idx == n_pipe - 1, outputs, 0)
        outputs = jax.lax.psum(outputs, pipe_axis)
        return outputs

    out_mb = run(stacked_params, x_mb)
    return out_mb.reshape(B, *x.shape[1:])

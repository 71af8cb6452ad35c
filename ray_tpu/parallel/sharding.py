"""Logical-axis sharding rules.

Models annotate arrays with *logical* axis names ("batch", "embed",
"heads", …); a rule table maps those to mesh axes.  Swapping the rule
table re-shards the whole model (DP↔FSDP↔TP↔…) without touching model
code — the t5x/flax-partitioning idea, self-contained here.

The reference has no analogue (its TP/SP slots are empty, SURVEY.md
§2.3); this is the TPU-native mechanism that fills them.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# A logical axis maps to: one mesh axis, a tuple of mesh axes (the dim
# is sharded over their product), or None (replicated).
Rule = Tuple[str, Union[str, Tuple[str, ...], None]]


def axes_entry(axes: Sequence[str]):
    """Mesh axes of one dim as a PartitionSpec's entry."""
    return None if not axes else axes[0] if len(axes) == 1 else tuple(axes)


class ShardingRules:
    """Ordered logical-axis → mesh-axis mapping."""

    def __init__(self, *rules: Rule):
        self._table = dict(rules)

    def mesh_axes(self, logical: Optional[str]):
        if logical is None:
            return None
        return self._table.get(logical)

    def axes(self, logical_axes: Sequence[Optional[str]]
             ) -> Tuple[Tuple[str, ...], ...]:
        """Per dim of an array with these logical names, the mesh axes it
        is sharded over (``()``: replicated).

        A mesh axis may appear at most once across the dims of one
        array; later duplicates fall back to replication.
        """
        used = set()
        out = []
        for name in logical_axes:
            axes = self.mesh_axes(name) or ()
            if isinstance(axes, str):
                axes = (axes,)
            axes = tuple(a for a in axes if a not in used)
            used.update(axes)
            out.append(axes)
        return tuple(out)

    def spec(self, logical_axes: Sequence[Optional[str]]) -> P:
        """PartitionSpec for a tuple of per-dim logical names."""
        parts = [axes_entry(axes) for axes in self.axes(logical_axes)]
        while parts and parts[-1] is None:
            parts.pop()
        return P(*parts)

    def extended(self, *rules: Rule) -> "ShardingRules":
        new = ShardingRules()
        new._table = {**self._table, **dict(rules)}
        return new


# Default rules for transformer LMs (scaling-book recipe):
#  - activations: batch over (data, fsdp); seq over seq (context
#    parallel); heads/mlp over tensor.
#  - weights: embed dim over fsdp (ZeRO-3 gather per layer), output
#    feature dims over tensor (megatron), experts over expert.
#  - "layers" shards a lax.scan-stacked weight tree over pipe stages.
DEFAULT_RULES = ShardingRules(
    ("batch", ("data", "fsdp")),
    ("seq", "seq"),
    ("act_embed", "tensor"),
    ("heads", "tensor"),
    ("kv_heads", "tensor"),
    ("head_dim", None),
    ("embed", "fsdp"),
    ("mlp", "tensor"),
    ("vocab", "tensor"),
    ("expert", "expert"),
    ("layers", "pipe"),
)


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: ShardingRules = DEFAULT_RULES


_ctx = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    # Thread-local only: NamedSharding carries its mesh, so no jax-global
    # mesh context is required (and jax 0.9 renamed that API anyway).
    prev = _ctx.mesh
    _ctx.mesh = mesh
    try:
        yield mesh
    finally:
        _ctx.mesh = prev


@contextlib.contextmanager
def use_sharding_rules(rules: ShardingRules):
    prev = _ctx.rules
    _ctx.rules = rules
    try:
        yield rules
    finally:
        _ctx.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _ctx.mesh


def current_rules() -> ShardingRules:
    return _ctx.rules


def logical_sharding(logical_axes: Sequence[Optional[str]],
                     mesh: Optional[Mesh] = None,
                     rules: Optional[ShardingRules] = None) -> NamedSharding:
    mesh = mesh or _ctx.mesh
    if mesh is None:
        raise ValueError("no mesh: pass one or enter use_mesh(...)")
    rules = rules or _ctx.rules
    return NamedSharding(mesh, rules.spec(logical_axes))


@contextlib.contextmanager
def suppress_constraints():
    """Disable with_logical_constraint within the block — used while
    tracing code placed inside a fully-manual shard_map region, where
    global sharding constraints don't apply (the shard_map specs own
    the layout)."""
    prev = getattr(_ctx, "suppress", False)
    _ctx.suppress = True
    try:
        yield
    finally:
        _ctx.suppress = prev


def partitioning_mesh() -> Optional[Mesh]:
    """The mesh the SPMD partitioner lays the traced code out over: the
    active one, or None where there is nothing to lay out -- no mesh, one
    device, or a manual region (suppress_constraints: the enclosing
    shard_map's specs own the layout)."""
    mesh = _ctx.mesh
    if mesh is None or mesh.size == 1 or getattr(_ctx, "suppress", False):
        return None
    return mesh


def with_logical_constraint(x, *logical_axes: Optional[str],
                            rules: Optional[ShardingRules] = None):
    """``lax.with_sharding_constraint`` by logical axis names.

    No-op outside a mesh context so model code runs unchanged on a
    single device (tests, single-chip bench), and under
    suppress_constraints() (inside shard_map bodies).
    """
    mesh = partitioning_mesh()
    if mesh is None:
        return x
    rules = rules or _ctx.rules
    spec = rules.spec(logical_axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_over_mesh(fn, in_axes: Sequence[Sequence[Optional[str]]],
                    out_axes: Sequence[Optional[str]]):
    """``fn`` run once per shard of the active mesh: ``jax.shard_map``
    with its specs resolved from logical axis names.  Returns ``fn``
    itself when no mesh is active, the mesh has one device, or the
    caller is already inside a manual region (suppress_constraints).

    For ops the SPMD partitioner cannot split — Pallas kernels: a bare
    ``pallas_call`` inside jit on sharded operands is handed the GLOBAL
    arrays, so every device gathers all of them and does all the
    work."""
    mesh = partitioning_mesh()
    if mesh is None:
        return fn
    rules = _ctx.rules
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(rules.spec(axes) for axes in in_axes),
        out_specs=rules.spec(out_axes), check_vma=False)


def shard_params(params, logical_axes_tree, mesh: Optional[Mesh] = None,
                 rules: Optional[ShardingRules] = None):
    """Device-put a param pytree according to a matching pytree of
    logical-axis tuples (None leaves replicate)."""
    mesh = mesh or _ctx.mesh
    rules = rules or _ctx.rules

    def place(x, axes):
        if mesh is None:
            return x
        spec = rules.spec(axes) if axes is not None else P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(place, params, logical_axes_tree,
                        is_leaf=lambda v: v is None)

"""Mixture-of-Experts FFN: dropless grouped matmuls, and a dense
dispatch for an ``expert`` mesh axis.

Reference: Ray implements NO MoE/EP (SURVEY §2.3 — it only offers
placement-group primitives); the TPU build must supply the strategy
natively.  Two formulations share one router (``_route``):

- ``moe_ffn_dropless`` — what every path but expert-parallel training
  runs (``llama.forward``, ``prefill_forward``, ``forward_with_cache``,
  the serve programs).  The ``T x K`` (token, expert) assignments are
  sorted by expert, the sorted rows go through three grouped matmuls
  (``jax.lax.ragged_dot`` over the ``[E, D, H]`` stacks; on a TPU XLA
  lowers it to a Mosaic kernel that reads each touched expert's tiles
  in place), are un-sorted and summed under their gates.  Every token
  reaches all of its K experts whatever the load; rows marked not
  ``valid`` (prompt padding, inactive decode slots) are sorted behind
  every group and take no expert's rows.
- ``moe_ffn`` — GShard/Switch-style DENSE dispatch with a capacity:
  top-k routing builds a (tokens, experts, capacity) one-hot dispatch
  tensor, so dispatch/combine are einsums with static shapes, and
  sharding the expert dimension over the ``expert`` mesh axis makes XLA
  lower them to all_to_all over ICI.  Tokens beyond an expert's
  capacity are dropped (the residual path carries them).  Kept ONLY as
  what a mesh with ``expert > 1`` uses in training.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.parallel.sharding import with_logical_constraint

PyTree = Any


def relu2(x: jax.Array) -> jax.Array:
    """``relu(x)^2``: the activation of an expert of two matrices."""
    return jnp.square(jax.nn.relu(x))


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    hidden_size: int
    intermediate_size: int
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    # Renormalise the K chosen gates to sum to one (Switch, Mixtral), or
    # use the softmax's probabilities as they are (OLMoE's
    # ``norm_topk_prob: false``).
    norm_topk: bool = True
    # The experts' gate activation: "silu" (SwiGLU) or "relu" (ReGLU); or
    # "relu2": an expert of TWO matrices, ``relu(x W_up)^2 W_down``, whose
    # params hold no ``w_gate`` (``moe_ffn_dropless``'s forward alone).
    activation: str = "silu"
    dtype: Any = jnp.bfloat16
    # Group-limited routing (DeepSeek-V2's group_limited_greedy): the
    # experts are ``groups`` groups of consecutive ones, a group scores
    # its best expert, and only the ``top_groups`` best groups' experts
    # can be chosen (0: no groups).  The chosen gates x ``routed_scale``.
    groups: int = 0
    top_groups: int = 0
    routed_scale: float = 1.0
    # ``(first, count)``: the experts whose matrices are HERE (one chip's
    # share; the stacks are ``[count, ...]``), of the ``n_experts`` the
    # router scores.  (): all of them.
    held: Tuple[int, ...] = ()
    # How the router scores an expert: "softmax" over all of them, or
    # "sigmoid" each alone.  With a ``router_bias`` leaf in the params the
    # top-k are those of score + bias and the gates the chosen scores.
    score: str = "softmax"

    @property
    def act(self):
        return {"silu": jax.nn.silu, "relu": jax.nn.relu,
                "relu2": relu2}[self.activation]

    @property
    def gated(self) -> bool:
        """Whether an expert (and a shared one beside it) has a gate matrix
        beside ``w_up`` and ``w_down``: all but "relu2".  The one place the
        gate's absence is read from the activation."""
        return self.activation != "relu2"


def init_moe_params(rng: jax.Array, config: MoEConfig,
                    dtype=jnp.float32) -> PyTree:
    from ray_tpu.models.llama import init_dense

    c = config
    k_router, k_gate, k_up, k_down = jax.random.split(rng, 4)

    def dense(key, shape, fan_in):
        return init_dense(key, shape, fan_in, dtype)

    E, D, H = c.n_experts, c.hidden_size, c.intermediate_size
    return {
        "router": dense(k_router, (D, E), D),
        "w_gate": dense(k_gate, (E, D, H), D),
        "w_up": dense(k_up, (E, D, H), D),
        "w_down": dense(k_down, (E, H, D), H),
    }


def moe_param_logical_axes() -> Dict[str, Tuple]:
    return {
        "router": (None, "expert"),
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
    }


def _einsum(eq, *args):
    """bf16×bf16 einsum with f32 MXU accumulation (same measured
    rationale as llama.matmul: operand-dtype accumulation drops XLA
    onto a ~4-5x slower path)."""
    out = jnp.einsum(eq, *args, preferred_element_type=jnp.float32)
    return out.astype(args[0].dtype)


@jax.named_scope("router")
def _route(xt: jax.Array, router: jax.Array, k: int, norm_topk: bool,
           groups: int = 0, top_groups: int = 0, scale: float = 1.0,
           score: str = "softmax", bias: Optional[jax.Array] = None):
    """The one routing function (dropless, dense dispatch and the parity
    reference cannot drift): f32 softmax over all experts, top-k, the k
    gates renormalised to sum to one or left as they are, then x
    ``scale``.  With ``groups`` the top-k is taken among the experts of
    the ``top_groups`` groups whose best expert scores highest; the
    others' probabilities count as 0.  ``score`` "sigmoid": an expert's
    score is the sigmoid of its own logit.  ``bias`` (E,) float32: the k
    experts are those with the largest score + bias, the gates their
    SCORES without it, renormalised over ``sum + 1e-6`` (LFM2)."""
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                        router.astype(jnp.float32))
    if score == "sigmoid" or bias is not None:
        probs = jax.nn.sigmoid(logits) if score == "sigmoid" \
            else jax.nn.softmax(logits, axis=-1)
        choose_from = probs if bias is None \
            else probs + bias.astype(jnp.float32)
        _, expert_idx = jax.lax.top_k(choose_from, k)
        gate_vals = jnp.take_along_axis(probs, expert_idx, axis=-1)
        if norm_topk:
            gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-6)
        if scale != 1.0:
            gate_vals = gate_vals * scale
        return probs, gate_vals, expert_idx
    probs = jax.nn.softmax(logits, axis=-1)          # (T, E)
    choose_from = probs
    if groups:
        T, E = probs.shape
        best = probs.reshape(T, groups, E // groups).max(-1)   # (T, G)
        _, kept = jax.lax.top_k(best, top_groups)
        in_kept = jnp.zeros((T, groups), bool).at[
            jnp.arange(T)[:, None], kept].set(True)
        choose_from = jnp.where(jnp.repeat(in_kept, E // groups, axis=1),
                                probs, 0.0)
    gate_vals, expert_idx = jax.lax.top_k(choose_from, k)  # (T, K)
    if norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        gate_vals = gate_vals * scale
    return probs, gate_vals, expert_idx


@jax.named_scope("router")
def _aux_loss(probs: jax.Array, expert_idx: jax.Array) -> jax.Array:
    """Switch load-balancing loss: E * sum_e(share of tokens whose first
    choice is e * mean router probability of e)."""
    E = probs.shape[-1]
    top1 = jax.nn.one_hot(expert_idx[:, 0], E, dtype=jnp.float32)
    return E * jnp.sum(top1.mean(0) * probs.mean(0))


@jax.custom_vjp
def _computed_rows(rows: jax.Array, computed: jax.Array) -> jax.Array:
    """``rows`` as they are; their COTANGENT zeroed past the first
    ``computed`` rows.  A grouped matmul computes the rows of its groups
    alone, in the backward pass too: what it hands back for a row behind
    every group (an assignment elsewhere, a row that is not real) is
    whatever the buffer held -- seen as NaN on a v5e, PR 57 -- and must not
    be scattered into the stream's gradient.  Nothing in the forward."""
    return rows


def _computed_rows_fwd(rows, computed):
    return rows, computed


def _computed_rows_bwd(computed, g):
    mine = jnp.arange(g.shape[0], dtype=jnp.int32)[:, None] < computed
    return jnp.where(mine, g, jnp.zeros_like(g)), None


_computed_rows.defvjp(_computed_rows_fwd, _computed_rows_bwd)


def _rows_per_group(group_of_row: jax.Array, groups: int) -> jax.Array:
    """(groups,) int32: how many rows name each group; a row that names
    ``groups`` or more counts for none."""
    return jnp.sum(
        group_of_row[:, None] == jnp.arange(
            groups, dtype=group_of_row.dtype)[None, :],
        axis=0, dtype=jnp.int32)


def compact_rows(tokens: int, config: MoEConfig) -> int:
    """``C``: how many of a dispatch's sorted ``tokens x top_k`` rows a
    TRAINING share moves -- twice the even share of its held experts, in
    whole sublane tiles, at most all of them (which a configuration that
    holds half or more of the experts, or all, gets: nothing to compact)."""
    assignments = tokens * config.top_k
    if not config.held:
        return assignments
    bound = -(-2 * assignments * config.held[1] // config.n_experts)
    return min(-(-bound // 8) * 8, assignments)


def _grouped(rows, w, group_sizes, out_dtype=jnp.float32):
    # f32 accumulation, as llama.matmul
    return jax.lax.ragged_dot(rows, w, group_sizes,
                              preferred_element_type=out_dtype)


def _sorted_ffn(c: MoEConfig, training: bool, xt, w_gate, w_up, w_down,
                gate_vals, order, group_of_row, group_sizes) -> jax.Array:
    """The experts' part of xt (T, D) -> (T, D) float32 over ALL ``T x K``
    assignments in their sorted ``order``: gathered, through the three
    grouped matmuls (which compute their groups' rows alone), un-sorted
    and summed under the gates.  ``group_of_row`` (T*K,): what ``order``
    sorts, a share's held count where no group computes the assignment."""
    T, D = xt.shape
    K = c.top_k
    dt = c.dtype

    def guarded(rows):     # sorted first: the groups' rows
        return _computed_rows(rows, jnp.sum(group_sizes)) if training \
            else rows

    with jax.named_scope("expert_dispatch"):
        # Sorted rows in whole sublane tiles: at 22 picks of a few decode
        # slots (44 rows) the v5e compiler fails on the grouped matmul's
        # group sizes where a stack's ``L * E`` is no power of two
        # (INTERNAL, "Bitcast cannot have different shape sizes").  The rows
        # added lie past every group: no matmul computes them and
        # ``inverse`` names none.  Every benchmark cell's rows are whole
        # tiles already.
        spare = -(T * K) % 8
        gathered = jnp.pad(order, (0, spare)) if spare else order
        rows = guarded(xt[gathered // K])         # (T*K [+ spare], D)
    with jax.named_scope("expert_ffn"):
        if c.gated:
            act = guarded(
                c.act(_grouped(rows, w_gate, group_sizes).astype(dt))
                * _grouped(rows, w_up, group_sizes).astype(dt))
        else:
            act = guarded(
                c.act(_grouped(rows, w_up, group_sizes)).astype(dt))
        # (T*K, D) float32 (the benchmark's test of cell 5 finds its three
        # kernels as ``f32[``); a share's in ``dt``: 73,728 rows of 5,120,
        # three in four of them not computed, are 1.5 GB in float32
        out = _grouped(act, w_down, group_sizes,
                       dt if c.held else jnp.float32)
    # Un-sort (order is a permutation) and sum under the gates in float32:
    # K gathers of (T, D), a token's k-th result from wherever the sort put
    # it, written in the stream's type.  Un-sorted whole, every row is copied
    # once more before the sum: as (T, K, D) the chip pads K = 6 to a
    # sublane tile, as (T, K x D) a row changes its tile.
    with jax.named_scope("expert_dispatch"):
        inverse = jnp.zeros_like(order).at[order].set(jnp.arange(
            T * K, dtype=order.dtype), unique_indices=True).reshape(T, K)

        def result(k):
            mine = out[inverse[:, k]].astype(dt)
            if c.held:    # an assignment elsewhere was not computed: select
                here = group_of_row.reshape(T, K)[:, k, None] < c.held[1]
                mine = jnp.where(here, mine, 0)
            return mine.astype(jnp.float32) * gate_vals[:, k, None]

        return sum(result(k) for k in range(K))


def _block(c: MoEConfig, b, xt, gate_vals, order, group_sizes):
    """Block ``b`` of ``C`` sorted assignments (``compact_rows``; ``order``
    padded to whole blocks): ``(the assignments (C,), their tokens (C,),
    their rows of xt (C, D), their gates (C,), mine (C, 1) bool: the rows
    some group computes, the groups' sizes INSIDE the block)``."""
    C = compact_rows(xt.shape[0], c)
    first = jax.lax.dynamic_slice(order, (b * C,), (C,))
    token = first // c.top_k
    # (a row of the padding is past every group: its index 0 is never read
    # as an assignment)
    mine = (b * C + jnp.arange(C, dtype=jnp.int32)
            < jnp.sum(group_sizes))[:, None]
    ends = jnp.cumsum(group_sizes)
    inside = jnp.clip(ends, b * C, (b + 1) * C) \
        - jnp.clip(ends - group_sizes, b * C, (b + 1) * C)
    return (first, token, xt.at[token].get(mode="promise_in_bounds"),
            gate_vals.reshape(-1)[first], mine, inside)


def _blocks(c: MoEConfig, xt, order, group_sizes):
    """``(order padded to whole blocks of C, how many blocks hold a row of
    some group: 1 wherever the held experts' rows fit C)``."""
    C = compact_rows(xt.shape[0], c)
    return (jnp.pad(order, (0, -order.shape[0] % C)),
            (jnp.sum(group_sizes) + C - 1) // C)


def _held_rows_forward(c: MoEConfig, keep: bool, xt, w_gate, w_up, w_down,
                       gate_vals, order, group_sizes):
    """``_held_rows_ffn``; with ``keep`` also, over every block's rows,
    the two products between the matmuls: what ``_held_rows_ffn_bwd`` reads
    again.  The gathered rows and the third matmul's result it computes
    anew (~1 ms a layer of cell 13's step): kept as well they are two more
    full-size buffers, 0.5 GB of the step's scratch in cell 13, filled
    with zeros a pass (PERF.md section 6, PR 58)."""
    dt = c.dtype
    order, blocks = _blocks(c, xt, order, group_sizes)

    def block(carry):
        b, total, kept = carry
        with jax.named_scope("expert_dispatch"):
            _, token, rows, gates, mine, inside = _block(
                c, b, xt, gate_vals, order, group_sizes)
        with jax.named_scope("expert_ffn"):
            gate = _grouped(rows, w_gate, inside).astype(dt)
            up = _grouped(rows, w_up, inside).astype(dt)
            out = _grouped(c.act(gate) * up, w_down, inside, dt)
            kept = tuple(
                jax.lax.dynamic_update_slice(k, new, (b * new.shape[0], 0))
                for k, new in zip(kept, (gate, up)))
        with jax.named_scope("expert_dispatch"):
            # (a row no group computes holds what the buffer held)
            total = total.at[token].add(
                jnp.where(mine, out, 0).astype(jnp.float32)
                * gates[:, None], mode="promise_in_bounds")
        return b + 1, total, kept

    nothing = jnp.zeros((order.shape[0], w_gate.shape[-1]), dt)
    _, total, kept = jax.lax.while_loop(
        lambda carry: carry[0] < blocks, block,
        (jnp.int32(0), jnp.zeros(xt.shape, jnp.float32),
         (nothing, nothing) if keep else ()))
    return total, kept


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_rows_ffn(c: MoEConfig, xt, w_gate, w_up, w_down, gate_vals,
                   order, group_sizes) -> jax.Array:
    """``_sorted_ffn`` of a training share that holds under half the
    experts, exact for every routing (dropless): the sorted rows go through
    in blocks of ``C`` (``compact_rows``) -- gathered, multiplied and added
    back to their tokens under their gates (float32) -- and the loop ends
    with the last block that holds a row of some group: ONE block wherever
    the held experts' rows fit ``C``, so the dispatch moves ``C`` rows and
    not ``T x K``.  The loop's length is known at run time alone, so the
    backward pass is written out (``_held_rows_ffn_bwd``)."""
    return _held_rows_forward(c, False, xt, w_gate, w_up, w_down, gate_vals,
                              order, group_sizes)[0]


def _held_rows_ffn_fwd(c, xt, w_gate, w_up, w_down, gate_vals, order,
                       group_sizes):
    total, kept = _held_rows_forward(c, True, xt, w_gate, w_up, w_down,
                                     gate_vals, order, group_sizes)
    return total, (kept, xt, w_gate, w_up, w_down, gate_vals, order,
                   group_sizes)


def _held_rows_ffn_bwd(c, saved, g):
    """The cotangents of (xt, the three stacks, gate_vals) for ``g`` (T, D)
    float32, block by block: op for op what differentiating ``_sorted_ffn``
    gives on a block's rows, and no row past the groups' count hands
    anything back (``_computed_rows``)."""
    kept, xt, w_gate, w_up, w_down, gate_vals, order, group_sizes = saved
    dt = c.dtype
    f32 = jnp.float32
    order, blocks = _blocks(c, xt, order, group_sizes)

    def block(carry):
        b, d_xt, d_w_gate, d_w_up, d_w_down, d_gate_vals = carry

        def grouped_vjp(rows, w, ct, out_dtype=f32):
            # (the vjp's own product is dead code)
            return jax.vjp(lambda rows, w: _grouped(
                rows, w, inside, out_dtype), rows, w)[1](ct)

        with jax.named_scope("expert_dispatch"):
            first, token, rows, gates, mine, inside = _block(
                c, b, xt, gate_vals, order, group_sizes)
            g_rows = g.at[token].get(mode="promise_in_bounds")  # (C, D)
            d_out = jnp.where(mine, g_rows * gates[:, None], 0).astype(dt)
        with jax.named_scope("expert_ffn"):
            gate, up = (jax.lax.dynamic_slice(
                k, (b * rows.shape[0], 0), (rows.shape[0], k.shape[1]))
                for k in kept)
            act, act_vjp = jax.vjp(
                lambda gate, up: c.act(gate) * up, gate, up)
            out = _grouped(act, w_down, inside, dt)
            d_act, d_down = grouped_vjp(act, w_down, d_out, dt)
            d_gate, d_up = (jnp.where(mine, d, 0).astype(f32)
                            for d in act_vjp(d_act))
            d_rows, d_gate_w = grouped_vjp(rows, w_gate, d_gate)
            d_rows_up, d_up_w = grouped_vjp(rows, w_up, d_up)
        with jax.named_scope("expert_dispatch"):
            d_gates = jnp.sum(
                g_rows * jnp.where(mine, out, 0).astype(f32), axis=-1)
            return (b + 1,
                    d_xt.at[token].add(
                        jnp.where(mine, d_rows + d_rows_up, 0),
                        mode="promise_in_bounds"),
                    d_w_gate + d_gate_w, d_w_up + d_up_w,
                    d_w_down + d_down,
                    d_gate_vals.at[first].add(d_gates))

    _, d_xt, d_w_gate, d_w_up, d_w_down, d_gate_vals = jax.lax.while_loop(
        lambda carry: carry[0] < blocks, block,
        (jnp.int32(0), *(jnp.zeros_like(a)
                         for a in (xt, w_gate, w_up, w_down)),
         jnp.zeros((gate_vals.size,), f32)))
    return (d_xt, d_w_gate, d_w_up, d_w_down,
            d_gate_vals.reshape(gate_vals.shape), None, None)


_held_rows_ffn.defvjp(_held_rows_ffn_fwd, _held_rows_ffn_bwd)


def moe_ffn_dropless(x: jax.Array, params: PyTree, config: MoEConfig,
                     valid: Optional[jax.Array] = None,
                     layer_index: Optional[jax.Array] = None,
                     route_x: Optional[jax.Array] = None,
                     training: bool = False
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) → (out (B, S, D), aux_loss scalar, expert_rows (E,)
    int32: the rows each expert computed).

    With ``config.held`` = (first, count) the expert matrices are those of
    experts ``first .. first + count`` alone and the result is THEIR part
    of the layer's: a token's assignments to experts elsewhere are dropped
    before the sort, so the grouped matmuls' rows are the held experts'
    rows; ``expert_rows`` is then (count + 1,): the held experts' rows
    and, LAST, the count of real assignments routed elsewhere.

    ``valid`` (broadcastable to (B, S), bool): rows that are real.  The
    others (prompt padding, inactive decode slots) get a zero and are
    assigned to no expert, so the grouped matmuls do not compute them
    and ``expert_rows`` does not count them.

    ``layer_index``: the three expert matrices in ``params`` are then the
    whole ``[L, E, ...]`` stacks of a layer-stacked model and this is the
    layer to use.  The stack is handed to the grouped matmul as ``L * E``
    groups of which only this layer's are non-empty: the kernel reads the
    touched experts' tiles from the stack where it lies.  (Slicing a
    layer out first makes XLA copy ``[E, D, H]`` per matrix per layer,
    since a custom call cannot read through a dynamic slice.)  The
    router in ``params`` is the layer's own either way.

    ``route_x`` (B, S, width of the router): what the router reads where
    that is not the rows the experts multiply (a router placed before
    attention; the full-width stream of experts that work in a latent).

    ``training``: what a backward pass and the balance update of a
    selection bias need, beside a forward that serving lowers as it always
    did.  ``expert_rows`` is (n_experts,), the real rows' choices counted
    over EVERY expert the router scores, held here or not; the rows that
    no group computes hand back no cotangent (``_computed_rows``); and a
    share of under half the experts gathers, multiplies and adds back its
    ``T x K`` sorted rows in blocks of ``compact_rows``, as many as hold a
    held expert's row: one wherever they fit it (``_held_rows_ffn``)."""
    c = config
    B, S, D = x.shape
    T = B * S
    K = c.top_k
    # the groups of the grouped matmuls: the experts held here
    first, E = c.held or (0, c.n_experts)
    dt = c.dtype
    xt = x.reshape(T, D).astype(dt)

    probs, gate_vals, expert_idx = _route(
        xt if route_x is None else route_x.reshape(T, -1),
        params["router"], K, c.norm_topk, c.groups, c.top_groups,
        c.routed_scale, c.score, params.get("router_bias"))
    with jax.named_scope("expert_dispatch"):
        flat = expert_idx.reshape(T * K)
        if valid is not None:
            valid = jnp.broadcast_to(valid, (B, S)).reshape(T)
        if c.held:
            # An assignment to an expert elsewhere is dropped BEFORE the
            # sort: behind every group, like a row that is not real, and
            # counted apart.
            flat = flat - first
            here = (flat >= 0) & (flat < E)
            real = True if valid is None else jnp.repeat(valid, K)
            elsewhere = jnp.sum(real & ~here, dtype=jnp.int32)
            flat = jnp.where(here, flat, E)
        if valid is not None:
            # behind every group: sorted last, counted by no expert
            flat = jnp.where(jnp.repeat(valid, K), flat, E)
        order = jnp.argsort(flat)        # stable: a token's K stay in order
        expert_rows = _rows_per_group(flat, E)

    with jax.named_scope("expert_ffn"):
        # (an expert without a gate has no ``w_gate``: None in its place)
        w_gate, w_up, w_down = (
            params[k].astype(dt) if c.gated or k != "w_gate" else None
            for k in ("w_gate", "w_up", "w_down"))
        group_sizes = expert_rows
        if layer_index is not None:
            L = w_up.shape[0]
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros((L * E,), jnp.int32), expert_rows,
                (layer_index * E,))
            w_gate, w_up, w_down = (
                None if w is None else w.reshape((L * E,) + w.shape[2:])
                for w in (w_gate, w_up, w_down))

    with jax.named_scope("expert_dispatch"):
        if training and c.held:
            chosen = expert_idx.reshape(T * K)
            if valid is not None:
                chosen = jnp.where(jnp.repeat(valid, K), chosen, c.n_experts)
            choices = _rows_per_group(chosen, c.n_experts)
    stacks = (w_gate, w_up, w_down)
    if training and not c.gated:
        raise NotImplementedError(
            "an expert of two matrices (relu2) is served only: the written-"
            "out backward of a held share multiplies by a gate")
    if training and compact_rows(T, c) < T * K:
        out = _held_rows_ffn(c, xt, *stacks, gate_vals, order, group_sizes)
    else:
        out = _sorted_ffn(c, training, xt, *stacks, gate_vals, order, flat,
                          group_sizes)
    with jax.named_scope("expert_dispatch"):
        if c.held:
            expert_rows = choices if training \
                else jnp.concatenate([expert_rows, elsewhere[None]])
        if valid is not None:
            out = jnp.where(valid[:, None], out, 0.0)
        out = out.reshape(B, S, D).astype(x.dtype)
    return out, _aux_loss(probs, expert_idx), expert_rows


def moe_ffn(x: jax.Array, params: PyTree, config: MoEConfig
            ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) → (out (B, S, D), aux_loss scalar).

    The dense-dispatch formulation with a capacity, which DROPS tokens
    past it.  It stays only as what a mesh with ``expert > 1`` uses in
    training: its sharding constraint on the expert dimension is what
    lowers to the all-to-all.  No serving path and no benchmark cell
    runs it; everything else routes through ``moe_ffn_dropless``.

    aux_loss is the Switch load-balancing loss (mean fraction of
    tokens per expert × mean router prob per expert × E); add it to
    the training loss scaled by ~1e-2."""
    c = config
    if c.held:
        raise NotImplementedError(
            "moe_ffn (dense dispatch under an expert mesh axis) holds "
            "every expert: a share of the experts (held) runs through "
            "moe_ffn_dropless")
    B, S, D = x.shape
    T = B * S
    E, K = c.n_experts, c.top_k
    dt = c.dtype
    xt = x.reshape(T, D).astype(dt)

    probs, gate_vals, expert_idx = _route(
        xt, params["router"], K, c.norm_topk, c.groups, c.top_groups,
        c.routed_scale, c.score, params.get("router_bias"))

    capacity = int(max(1, round(T * K / E * c.capacity_factor)))

    # Position of each (token, k) within its expert's capacity buffer:
    # cumulative count of prior assignments to the same expert.
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)  # (T,K,E)
    flat = onehot.reshape(T * K, E)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(T, K, E)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)            # (T, K)
    keep = pos < capacity

    # Dense dispatch tensor (T, E, C): 1 where token t goes to slot
    # (e, c).  combine = dispatch weighted by the gate.
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    t_idx = jnp.arange(T)[:, None].repeat(K, 1)
    dispatch = dispatch.at[
        t_idx.reshape(-1),
        expert_idx.reshape(-1),
        jnp.clip(pos, 0, capacity - 1).reshape(-1),
    ].add(keep.astype(jnp.float32).reshape(-1))
    gate_te = jnp.zeros((T, E), jnp.float32).at[
        t_idx.reshape(-1), expert_idx.reshape(-1)
    ].add((gate_vals * keep).reshape(-1))
    combine = dispatch * gate_te[:, :, None]

    # Expert inputs (E, C, D): the einsum's sharding constraint on the
    # expert dim is what turns this into an all_to_all over ICI.
    expert_in = _einsum("tec,td->ecd", dispatch.astype(dt), xt)
    expert_in = with_logical_constraint(expert_in, "expert", None, None)

    h = _einsum("ecd,edh->ech", expert_in, params["w_gate"].astype(dt))
    u = _einsum("ecd,edh->ech", expert_in, params["w_up"].astype(dt))
    act = c.act(h) * u
    expert_out = _einsum("ech,ehd->ecd", act,
                         params["w_down"].astype(dt))
    expert_out = with_logical_constraint(expert_out,
                                         "expert", None, None)

    out = _einsum("tec,ecd->td", combine.astype(dt), expert_out)

    return out.reshape(B, S, D).astype(x.dtype), \
        _aux_loss(probs, expert_idx)


def moe_ffn_reference(x: jax.Array, params: PyTree, config: MoEConfig
                      ) -> jax.Array:
    """Slow per-token loop-free reference (no capacity drops) for
    parity tests at small shapes: every token visits its top-k experts
    exactly."""
    c = config
    B, S, D = x.shape
    dt = c.dtype
    xt = x.reshape(-1, D).astype(dt)
    _probs, gate_vals, expert_idx = _route(
        xt, params["router"], c.top_k, c.norm_topk, c.groups, c.top_groups,
        c.routed_scale, c.score, params.get("router_bias"))

    def per_expert(e):
        h = xt.astype(dt) @ params["w_gate"][e].astype(dt)
        u = xt.astype(dt) @ params["w_up"][e].astype(dt)
        return (c.act(h) * u) @ params["w_down"][e].astype(dt)

    all_out = jnp.stack([per_expert(e)
                         for e in range(c.n_experts)])  # (E, T, D)
    out = jnp.zeros_like(xt, dtype=jnp.float32)
    for k in range(c.top_k):
        picked = all_out[expert_idx[:, k], jnp.arange(xt.shape[0])]
        out = out + gate_vals[:, k:k + 1] * picked.astype(jnp.float32)
    return out.reshape(B, S, D).astype(x.dtype)

"""What the chip executes when a llama-family config is served.

Every device program of ``serve/llm.py``'s scheduler is built here, one
``build_*`` function each, from a ``LlamaConfig`` alone (the paged
plane's from a ``BlockPool``: config, block size, block format): no
engine, thread or weights are needed to lower one.  By the cache kept:

- a per-slot cache (``init_cache``: K and V ``(La, B, S, Hkv, D)`` over
  the attention layers -- heads of 64 two a 128-lane row, and whole-lane
  heads too few for a sublane tile beside a state, as rows -- and,
  for a model with Mamba-2 layers, each slot's
  recurrent and conv states beside them, for one with short-convolution
  layers its conv states alone; for a model with window layers
  a pool of every position for its full layers and a ring of the last
  ``window_size`` for its window layers; for a model with latent
  attention ONE leaf of a latent row a token and layer in place of K and
  V; for a model with an indexer a pool of one index key a token and
  layer beside K and V): ``prefill``, ``decode_k``;
- a block pool ``(N, L, bs, Hkv, D)`` (``llama.init_paged_kv_cache``):
  ``prefill_cold``, ``prefill_warm``, ``decode_paged``, ``inject`` (and
  its inverse ``BlockPool.extract``), ``spec_verify``;
- the speculative draft's own dense cache: ``draft_prefill``,
  ``draft_propose``;
- no cache at all: ``seat``, a prefill group's first tokens and lengths
  into the token and length carries of ``decode_k`` / ``decode_paged``.

The prefills and ``spec_verify`` are ``llama.layer_walk`` with their own
K/V step, the decode step ``llama.layer_block`` under its own scan: what a
layer is made of is ``models/llama.py``'s business, in what layout its K/V
and states lie and what its queries attend is this module's.  The block pool and the
draft hold K/V alone: ``serve/llm.py`` refuses a config with state-space
layers on those planes.  The device trace names a program's module after
its inner function (``jit_prefill``, ``jit_decode_k``): the benchmark's
readers find them by that name.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import indexer, llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops.decode_attention import decode_attention, path_taken
from ray_tpu.ops.mla_decode_attention import mla_decode_attention


@jax.named_scope("expert_dispatch")
def _expert_load(expert_rows, held: bool = False):
    """What a device program hands back about its experts, read at the
    harvest that exists: ``expert_rows`` (..., L, E) int32, the rows each
    layer's experts computed, per step of a chunk or for a prefill group
    -> (the (L, E) histogram summed over the steps, the number of
    (step, layer, expert) triples that had a row).  ``()`` for a dense
    model, whose programs return nothing more than they did.  ``held``: a
    model that holds a share of its experts, whose rows end with the
    count routed to experts elsewhere (``moe.moe_ffn_dropless``); that
    count, summed, is then a third result and E the experts held."""
    if expert_rows is None:
        return ()
    rows = expert_rows.reshape((-1,) + expert_rows.shape[-2:])
    if held:
        rows, elsewhere = rows[..., :-1], rows[..., -1]
        return (rows.sum(0), jnp.sum(rows > 0, dtype=jnp.int32),
                elsewhere.sum())
    return (rows.sum(0), jnp.sum(rows > 0, dtype=jnp.int32))


# ------------------------------------------------------------ the cache
# THE place that knows what a dense-plane serving cache is made of.  The
# scheduler (serve/llm.py) holds the tree these functions build and hands
# it back to the programs; it never names a leaf.
def init_cache(cfg: LlamaConfig, slots: int, max_len: int):
    """The per-slot cache of a config: K and V ``(La, B, S, Hkv, D)``
    over its attention layers (heads of 64, an even number of them: two a
    128-lane row, as rows ``(La, B, S * Hkv / 2, 128)``, the same bytes; 2
    heads of 128 beside a state as rows ``(La, B, S * 2, 128)``:
    ``LlamaConfig.kv_as_rows``)
    and, for its Mamba layers, each slot's
    recurrent state ``ssm (Lm, B, N, nh x hd)`` (stored as
    ``cfg.ssm_state_dtype``) and conv window ``conv (Lm, K - 1, B,
    conv_dim)``; for its short-convolution layers ``conv (Lc, taps - 1,
    B, D)`` alone; for its KDA layers ``ssm (Lk, B, H, d, d)``, a matrix a
    head, and ``conv (Lk, K - 1, B, 3 H d)``; for its power-retention layers
    ``ssm (Lp, B, Hkv, d/2 + 2, d, d)`` alone (``ops/power_state_update.py``
    has the layout), and NO ``k`` or ``v`` where no layer attends.  A state is not positional: ``build_prefill`` replaces a
    slot's whole state, ``decode_step`` advances it in place."""
    if cfg.layers_of("window") or cfg.kv_layer is not None:
        # (a decoder-hybrid-decoder: the pools its kinds of layer ask for
        # side by side -- the K/V layer's rows, which its cross layers read
        # and do not own, the window layers' rings, the Mamba-1 states)
        cache = _init_window_cache(cfg, slots, max_len)
        if cfg.layers_of("mamba1"):
            from ray_tpu.models import mamba1

            cache.update(mamba1.init_state(cfg, cfg.layers_of("mamba1"),
                                           slots))
        return cache
    if cfg.kv_lora_rank:
        # Latent attention keeps ONE leaf: a row of ``latent_row`` values a
        # token and layer (``c_kv`` normed, ``k_rope`` roped, zeros to
        # whole lanes) in place of K and V a head.
        return {"latent": jnp.zeros(
            (cfg.n_layers, slots, max_len, cfg.latent_row), cfg.dtype)}
    if cfg.index_topk:
        return _init_indexed_cache(cfg, slots, max_len)
    if cfg.kv_as_rows:
        # Heads of 64: two a 128-lane row, as rows -- the bytes of ``(La,
        # B, S, Hkv, 64)`` in their order, in the one shape of them that
        # the decode kernel reads where they lie (``_attend_rows``).  And
        # whole-lane heads too few for a sublane tile (2 of 128), which by
        # position would be padded to one.
        cache = {name: _row_pool(cfg, cfg.layers_of("attention"), slots,
                                 max_len) for name in ("k", "v")}
    elif not cfg.attending_layers():
        # No layer attends: no K/V leaf at all (not one of no rows), and a
        # slot costs the same whatever ``max_len`` is, which then bounds
        # the positions a rope table is asked for and nothing else.
        cache = {}
    else:
        cache = llama.init_kv_cache(cfg, slots, max_len)
    if cfg.layers_of("mamba"):
        from ray_tpu.models import mamba2

        cache.update(mamba2.init_state(cfg, cfg.layers_of("mamba"), slots))
    if cfg.layers_of("conv"):
        from ray_tpu.models import shortconv

        cache.update(shortconv.init_state(cfg, cfg.layers_of("conv"),
                                          slots))
    if cfg.layers_of("kda"):
        from ray_tpu.models import kda

        cache.update(kda.init_state(cfg, cfg.layers_of("kda"), slots))
    if cfg.layers_of("power"):
        from ray_tpu.models import power_retention

        cache.update(power_retention.init_state(
            cfg, cfg.layers_of("power"), slots))
    return cache


def _init_indexed_cache(cfg: LlamaConfig, slots: int, max_len: int):
    """A model with an indexer keeps a third pool by position beside K
    and V: ``ik``, ONE index key a token and layer, TRANSPOSED -- ``(L, B,
    index_head_dim, positions)``, positions along lanes: a 64-wide minor
    dimension the chip would pad to 128 lanes, and the scores' matmul reads
    a slot's ``(Di, S)`` as it lies (``models/indexer.py``).  K and V are
    stored as the rows the decode kernel reads, ``(L, B, positions * Hkv,
    D)`` (``_init_window_cache``)."""
    layers = cfg.layers_of("attention")
    return {"k": _row_pool(cfg, layers, slots, max_len),
            "v": _row_pool(cfg, layers, slots, max_len),
            "ik": jnp.zeros((layers, slots, cfg.index_head_dim, max_len),
                            cfg.dtype)}


def _row_pool(cfg: LlamaConfig, layers: int, slots: int, positions: int):
    """A K or V pool stored as the rows the decode kernel reads."""
    return jnp.zeros((layers, slots, positions * cfg.kv_row_heads,
                      cfg.kv_row_dim), cfg.dtype)


def _init_window_cache(cfg: LlamaConfig, slots: int, max_len: int):
    """A model with window layers keeps two K/V pools: ``k`` / ``v``,
    every position of its full layers, and ``wk`` / ``wv``, a RING of
    the last ``ring_len`` positions of its window layers (position ``p``
    in row ``p mod ring_len``).  Both are stored as the rows the decode
    kernel reads, ``(layers, B, positions * Hkv, D)``: with 4 kv heads a
    ``(..., positions, 4, D)`` leaf is padded to the sublane tile and
    occupies a multiple of its bytes."""
    def pool(layers, positions):
        return _row_pool(cfg, layers, slots, positions)

    full = cfg.layers_of("attention"), max_len
    ring = cfg.layers_of("window"), ring_len(cfg, max_len)
    return {"k": pool(*full), "v": pool(*full),
            "wk": pool(*ring), "wv": pool(*ring)}


def ring_len(cfg: LlamaConfig, max_len: int) -> int:
    """Positions a window layer's ring holds a slot."""
    return min(cfg.window_size, max_len)


def cache_pools(cfg: LlamaConfig, slots: int, max_len: int):
    """``{pool: (bytes, storage type)}`` of ``init_cache``'s tree: ``kv``
    (K and V together) and, for a model with Mamba layers, ``ssm`` and
    ``conv``; for a model with window layers ``kv_full`` and
    ``kv_window``; for a model with latent attention ``latent`` alone;
    for a model with an indexer ``index_keys`` beside ``kv``; for a
    decoder-hybrid-decoder ``kv_full`` (the K/V layer's rows), ``kv_window``,
    ``ssm`` and ``conv``."""
    shapes = jax.eval_shape(lambda: init_cache(cfg, slots, max_len))
    pool_of = {"k": "kv_full" if "wk" in shapes else "kv",
               "wk": "kv_window", "ik": "index_keys"}
    pool_of.update(v=pool_of["k"], wv="kv_window")
    pools = {}
    for name, leaf in shapes.items():
        pool = pool_of.get(name, name)
        nbytes = int(leaf.size) * leaf.dtype.itemsize
        pools[pool] = (pools.get(pool, (0,))[0] + nbytes, str(leaf.dtype))
    return pools


def kv_rows(cfg: LlamaConfig, cache=None):
    """What ``serve.engine_build`` says of an engine's K/V
    (docs/observability.md): ``kv_row_heads`` rows of ``kv_row_dim`` lanes
    a position and layer as ``cache`` holds them (``init_cache``'s tree;
    None: by position and head, as the paged planes gather their blocks),
    and ``decode_attention``, what attends them on this backend: the Mosaic
    ``"kernel"`` or ``"xla"``.  {} for a latent cache, which keeps neither
    K nor V, and for a model without an attending layer."""
    if cfg.kv_lora_rank or not cfg.attending_layers():
        return {}
    as_rows = cache is not None and cache["k"].ndim == 4
    hkv, d = (cfg.kv_row_heads, cfg.kv_row_dim) if as_rows \
        else (cfg.n_kv_heads, cfg.head_dim)
    return {"kv_row_heads": hkv, "kv_row_dim": d,
            "decode_attention": path_taken(hkv, d, as_rows)}


def share_and_state(cfg: LlamaConfig):
    """What ``serve.engine_build`` says beside ``kv_rows`` of a model that
    keeps a state a slot or holds experts: ``state_bytes_per_slot`` (the
    recurrent and conv states of all its layers together), ``ssm_groups``
    of a Mamba-2 model, ``power_state_rows`` (the rows of ``phi`` a
    key/value head's state has as laid out), ``power_degree`` and
    ``attending_layers`` of a power-retention model, and ``experts_held`` of the ``experts_routed`` its
    router scores.  {} for a plain dense decoder."""
    facts = {}
    state = state_bytes_per_slot(cfg)
    if state:
        facts["state_bytes_per_slot"] = sum(state.values())
    if cfg.layers_of("mamba"):
        facts["ssm_groups"] = cfg.ssm_groups
    if cfg.layers_of("power"):
        from ray_tpu.models import power_retention

        facts.update(power_state_rows=power_retention.state_rows(cfg),
                     power_degree=power_retention.DEGREE,
                     attending_layers=cfg.attending_layers())
    if cfg.moe_experts:
        facts.update(experts_held=cfg.held_experts[1],
                     experts_routed=cfg.moe_experts)
    return facts


def state_bytes_per_slot(cfg: LlamaConfig):
    """``{"ssm": ..., "conv": ...}`` bytes one slot's states hold over
    all the layers that keep one (Mamba layers: both; short-convolution
    layers: ``conv`` alone; {} for a model without such layers): what a
    decode step reads and writes for a slot it advances."""
    return {pool: nbytes for pool, (nbytes, _) in
            cache_pools(cfg, 1, 1).items() if pool in ("ssm", "conv")}


@jax.named_scope("kv_write")
def insert_states(cache, states, slots):
    """A prefill group's final states into its slots, wholesale (a reused
    slot inherits nothing of the request before).  states: one array a
    state leaf of the cache, in ``_state_names``' order -- ``(recurrent
    (Lm, G, N, nh x hd), conv (Lm, K - 1, G, conv_dim))`` of Mamba layers,
    ``(conv (Lc, K - 1, G, D),)`` of short-convolution layers; slots (G,),
    a negative one drops its row."""
    names = _state_names(cache)
    B = cache[names[0]].shape[_SLOT_AXIS[names[0]]]
    rows = jnp.where(slots < 0, B, slots)      # out of range: dropped
    at = {1: (slice(None), rows), 2: (slice(None), slice(None), rows)}
    return {**cache, **{
        name: cache[name].at[at[_SLOT_AXIS[name]]].set(
            new.astype(cache[name].dtype), mode="drop", unique_indices=True)
        for name, new in zip(names, states)}}


def decode_step(cfg: LlamaConfig, params, s_active: int, active,
                keep_logits: bool = False) -> Callable:
    """The shared per-token decode step (scan body): a row write of
    each slot's new K/V at its current position, cache attention
    over the row's keys among the first ``s_active`` positions
    (``ops/decode_attention.py``: one Mosaic call a layer whose operand
    is the whole cache; over rows of two heads of 64 the query goes in as
    wide as a row, ``_attend_rows``), greedy argmax fed back in-graph.
    The carry holds the WHOLE stacked (L, B, S, Hkv, D) K and V through
    the token loop and the layer loop, so XLA's while loops alias them in
    place: a step reads each live row's keys once, as far as the row is
    long, and writes B rows per layer; nothing of the cache's, a layer's or a
    prefix's shape is made (120 rows scatter in ~15 us on a v5e;
    PERF.md section 5; ``tests/test_decode_inplace.py`` holds it at the
    real widths).  ``s_active`` bounds the keys of a row that has run
    past its bucket.  IDENTICAL math for the dense
    cache and the paged gathered layout — block ordering makes
    gathered index == absolute position, which is what keeps the
    two planes' tokens bit-identical.  The speculative DRAFT model
    runs this step with its own ``cfg`` on its own dense cache.  The
    step's ys are ``(tokens, expert rows)``: the (L, E) rows each
    layer's experts computed, None for a dense model (and, with
    ``keep_logits``, the step's (B, V) logits: what a check against a
    reference reads; no serving program asks for them).  Experts compute
    ``active`` slots only, and read their ``[L, E, ...]`` matrices in
    place (the stacks are closed over, not sliced by the layer scan).

    The layer scan runs a PERIOD of ``cfg.layer_pattern`` an iteration,
    each layer ``llama.layer_block`` with this step's closures over the
    carry.  A Mamba-2 layer advances its layer of the stacked recurrent
    and conv states, which ride the carry after the lengths -- ``(ck, cv,
    tok, lens, ssm, conv)`` -- through both loops and are updated in
    place (``mamba2.decode``, ``ops/ssm_state_update.py``); an inactive
    slot's states are left as they are.  A short-convolution layer does
    the same with the one state it keeps, ``(ck, cv, tok, lens, conv)``, a
    KDA layer with its matrix states and conv tails
    (``ops/kda_state_update.py``).
    A model with window layers carries its two pools as rows (``init_
    cache``): ``ck`` / ``cv`` the full layers' and, after the lengths,
    the window layers' rings; a window layer writes its new row at
    ``length mod ring`` and attends its first ``min(length + 1, ring)``
    ring rows through the same kernel.  A model with latent attention
    carries its one leaf where K lies (no V): a layer writes the new
    latent row and attends ABSORBED (``ops/mla_decode_attention.py``, one
    call a layer).  A model with an indexer carries its index keys after
    the lengths, ``(ck, cv, tok, lens, ik)``: a layer writes the new index
    key beside K and V, scores the row's index keys, selects, and attends
    the selected keys alone, the others masked (``attend_selected``).
    Leading dense layers, and each run of whole periods
    of a stack that is not one pattern throughout (``LlamaConfig.
    parts``), run as a scan of their own, one after the other, through
    the same body.

    The scan is this step's own, not ``llama.walk_layers``': the walk,
    handed K/V as a carry, compiled to the same sizes but not the same text
    as the program the cells have measured since PR 24 (PERF.md section 6)."""

    n_win, hkv = cfg.layers_of("window"), cfg.kv_row_heads
    # what rides the carry after the lengths, by name
    names = _carried_names(jax.eval_shape(lambda: init_cache(cfg, 1, 8)))
    # a decoder-hybrid-decoder's eight reads of the K/V layer's rows (its
    # own among them) are told apart from its window layers' by their scope
    full_scope = "cross_attention" if cfg.kv_layer is not None \
        else "attention"

    def step(carry, _):
        ck, cv, tok, lens, *state = carry
        x = llama.embed(params, tok, cfg)[:, None]
        sin, cos = llama.rope_for(lens[:, None], cfg)
        # Inactive slots MUST not write: an occupied slot that is not
        # in this launch (LLMServer.slot_waiting) may hold a
        # prefill's fresh rows, and a stale-position
        # write would corrupt them.  Nor does a slot
        # past the attended prefix.  Their row goes out of range
        # and the scatter drops it.  (Nor may an inactive slot's
        # recurrent state advance: mamba2.decode.)
        # (a model without an attending layer carries no K/V: ``ck`` is
        # None, nothing is written by position and ``s_active`` bounds
        # nothing)
        with jax.named_scope("kv_write"):
            rows = jnp.arange(tok.shape[0], dtype=jnp.int32)
            pos = None if ck is None else jnp.where(
                active & (lens < s_active), lens, ck.shape[2])
            scale = cfg.attn_scale
            at = {"attention": pos}       # out of range past any row
            if n_win:
                ring = state[names.index("wk")].shape[2] // hkv
                at["window"] = jnp.where(pos < ck.shape[2], lens % ring,
                                         ring)

        # ``part``: the stack this body walks (the leading dense layers or
        # a run of whole periods, ``LlamaConfig.parts``), as a plain
        # config; ``l0``: its first layer's index among all.
        def body(carry, period_and_index, part, sliced, stacks, l0):
            x, ck, cv, *rest = carry
            state = dict(zip(names, rest))
            # the scan output of the last Mamba-1 layer, for the gmu layers
            memory = rest[len(names)] if len(rest) > len(names) else None
            period, p = period_and_index
            n_of = part.period.count
            expert_rows = []
            for j, (kind, i, layer) in enumerate(
                    llama.period_layers(sliced, period, p, part)):
                layer = {**layer, **stacks}

                def attend_absorbed(cq, latent):
                    # Latent attention, ABSORBED: the row written, then
                    # every head's [q~ ; q_rope] against the rows as they
                    # lie, read once for scores and values both.
                    nonlocal ck
                    q_nope, q_rope = llama.latent_queries(
                        cq, llama._wq_b_heads(layer, part), sin, cos, part)
                    l = p + a0
                    ck = _write(ck, l, rows, pos, latent[:, 0, None])
                    q = llama.latent_absorb_query(
                        q_nope[:, 0], q_rope[:, 0], layer, part)
                    with jax.named_scope("attention"):
                        u = mla_decode_attention(
                            q, ck, l, lens, active, s_active=s_active,
                            scale=scale, v_width=part.kv_lora_rank)
                    return llama.latent_absorb_values(
                        u, layer, part)[:, None], None

                def attend_pool(q, kk, vv):
                    # The layer's pool: the carry's K/V or, for a window
                    # layer, the rings after the lengths.  A cross layer
                    # reads the K/V layer's rows and writes none.
                    nonlocal ck, cv
                    if kind == "window":
                        pk, pv = state["wk"], state["wv"]
                    else:
                        pk, pv = ck, cv
                    if kind != "cross":
                        # Write before attend: the new row is among the
                        # keys.
                        pk = _write(pk, l, rows, at[kind], kk[:, 0])
                        pv = _write(pv, l, rows, at[kind], vv[:, 0])
                    # The kernel reads the carry where it lies, each row
                    # as far as it is long; an inactive row's zeros are
                    # discarded below.
                    with jax.named_scope("attention" if kind == "window"
                                         else full_scope):
                        attn = _attend_rows(
                            q[:, 0], pk, pv, l, lens, active,
                            s_active=s_active, scale=scale,
                            hkv=hkv)[:, None]
                    if kind == "window":
                        state.update(wk=pk, wv=pv)
                    else:
                        ck, cv = pk, pv
                    return attn, None

                def attend_selected(q, kk, vv, index):
                    # Behind an indexer: the new index key written like K
                    # and V (write before score: the new key is among the
                    # candidates), the row's index keys scored whole, and
                    # of the row's keys only those selected attended --
                    # through the same kernel, which reads the rows as they
                    # lie and masks the others.  A row no longer than
                    # ``index_topk`` selects every key: the dense result.
                    nonlocal ck, cv
                    qi, ki, w = index
                    ck = _write(ck, l, rows, pos, kk[:, 0])
                    cv = _write(cv, l, rows, pos, vv[:, 0])
                    ik = _write_index_key(state["ik"], l, rows, pos,
                                          ki[:, 0])
                    state["ik"] = ik
                    seen = min(s_active, ik.shape[3])
                    with jax.named_scope("indexer"):
                        keys_t = jax.lax.dynamic_slice(
                            ik, (l, 0, 0, 0), (1,) + ik.shape[1:3] + (seen,))
                    keep = indexer.select(
                        indexer.scores(qi, keys_t[0], w)[:, 0],
                        jnp.where(active, jnp.minimum(lens + 1, seen), 0),
                        part.index_topk)
                    with jax.named_scope("sparse_attention"):
                        attn = _attend_rows(
                            q[:, 0], ck, cv, l, lens, active,
                            s_active=s_active, scale=scale, hkv=hkv,
                            keep=keep)[:, None]
                    return attn, None

                def state_step(mixer, h):
                    # A state-keeping layer (Mamba-2, Mamba-1, short
                    # convolution, KDA, power retention): its layer of the
                    # stacked states, in place.
                    nonlocal memory
                    m = llama.layer_index(p, n_of(kind), i)
                    if l0:
                        m = m + cfg.layers_before(l0, kind)
                    held = getattr(mixer, "HELD", ("ssm", "conv"))
                    out, *new = mixer.decode(
                        h, layer, part, *(state[n] for n in held), m, active,
                        *((sin, cos) if getattr(mixer, "ROPES", False)
                          else ()))
                    if kind == "mamba1":
                        *new, scan_output = new
                        if memory is not None:
                            memory = scan_output
                    state.update(zip(held, new))
                    return out, None

                # where the part's first layer of this kind lies in its
                # pool (a cross layer: the K/V layer before it)
                a0 = cfg.layers_before(l0, "attention") - 1 \
                    if kind == "cross" else cfg.layers_before(l0, kind)
                if kind == "cross":
                    l = a0
                elif kind in llama.ATTENDING_KINDS and not part.kv_lora_rank:
                    # ``attend_pool``'s ``l``: the layer's place in its pool
                    l = llama.layer_index(p, n_of(kind), i)
                    if a0:
                        l = l + a0
                x, _aux, rows_j, _ = llama.layer_block(
                    x, layer, kind, part, sin, cos,
                    attend_absorbed if part.kv_lora_rank
                    else attend_selected if part.index_topk
                    else attend_pool,
                    state_step, valid=active, at=(p, part.period_len, j),
                    memory=memory)
                expert_rows.append(rows_j)
            return (x, ck, cv, *(state[n] for n in names),
                    *(() if memory is None else (memory,))), \
                llama.stack_period(expert_rows, part)

        # The leading dense layers, if the model has them, then the
        # scanned stack: the same body over each part's own weights (a
        # dense part computes no expert's rows).
        with jax.named_scope("layer_scan"):
            expert_rows = []
            memory = (jnp.zeros((tok.shape[0], 1, cfg.ssm_inner),
                                jnp.float32),) \
                if cfg.layers_of("gmu") else ()
            for part, key, l0 in cfg.parts():
                sliced, stacks = llama.split_expert_stacks(params[key], part)
                (x, ck, cv, *state), rows_part = jax.lax.scan(
                    functools.partial(body, part=part, sliced=sliced,
                                      stacks=stacks, l0=l0),
                    (x, ck, cv, *state, *memory),
                    (llama.scanned_layers(sliced, part),
                     jnp.arange(part.n_layers // part.period_len,
                                dtype=jnp.int32)))
                state, memory = state[:len(names)], tuple(state[len(names):])
                expert_rows.append(llama.merge_periods(rows_part, part))
            expert_rows = llama.over_parts(expert_rows)
        with jax.named_scope("head"):
            x = llama.norm(x, params, "final_norm", cfg).astype(cfg.dtype)
            logits = llama.head_logits(x, params, cfg)[:, 0]
        with jax.named_scope("sample"):
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, tok)
            lens = lens + active.astype(jnp.int32)
        return (ck, cv, nxt, lens, *state), (nxt, expert_rows) + (
            (logits,) if keep_logits else ())

    return step


def _attend_rows(q, pk, pv, l, lens, active, *, hkv, **how):
    """``decode_attention`` of q (B, Hq, D) over layer ``l`` of a pool.
    Over rows of two heads of 64 (``LlamaConfig.kv_heads_a_row``; ``hkv``
    rows a position) the query goes in as wide as a row, as
    ``llama._paired_rows`` lays a differential pair's: a head of a row's
    first kv head carries its values on the left half and zeros on the
    right, of its second the other way round, so ``q . [k_2g | k_2g+1]``
    is the head's own score exactly, the ``Hq / hkv`` query heads that
    share a row are one grouped-query group of the kernel's, and of ``P @
    [v_2g | v_2g+1]`` a head keeps its own half.  Read off the shapes: a
    pool by position, or rows as wide as the query (whole heads, a
    differential pair's already wide queries), is attended as it is."""
    d = q.shape[-1]
    if pk.ndim == 5 or pk.shape[3] == d:
        return decode_attention(q, pk, pv, l, lens, active, hkv=hkv, **how)
    hq = q.shape[1]
    second = ((np.arange(hq) // (hq // (2 * hkv))) % 2 == 1)[:, None]
    zeros = jnp.zeros_like(q)
    wide = jnp.concatenate([jnp.where(second, zeros, q),
                            jnp.where(second, q, zeros)], axis=-1)
    out = decode_attention(wide, pk, pv, l, lens, active, hkv=hkv, **how)
    return jnp.where(second, out[..., d:], out[..., :d])


@jax.named_scope("kv_write")
def _write(pool, l, slots, pos, new):
    """``new`` (B, Hkv, D) as slot ``slots[b]``'s position ``pos[b]`` of
    layer ``l`` of a pool ``(layers, B, positions, Hkv, D)`` or of its
    rows ``(layers, B, positions * Hkv, D)`` (two heads of 64 a row: the
    same values as ``(B, Hkv / 2, 128)``); a position out of range writes
    nothing."""
    if pool.ndim == 4:
        new = new.reshape(new.shape[0], -1, pool.shape[3])
        hkv = new.shape[1]
        pos = pos[:, None] * hkv + jnp.arange(hkv, dtype=jnp.int32)[None, :]
        slots = slots[:, None]
    return pool.at[l, slots, pos].set(
        new.astype(pool.dtype), mode="drop", indices_are_sorted=True,
        unique_indices=True)


@jax.named_scope("kv_write")
def _write_index_key(pool, l, slots, pos, new):
    """``new`` (B, Di) as slot ``slots[b]``'s position ``pos[b]`` of layer
    ``l`` of an index-key pool ``(layers, B, Di, positions)``; a position
    out of range writes nothing."""
    return pool.at[l, slots, :, pos].set(
        new.astype(pool.dtype), mode="drop", unique_indices=True)


# What rides the carry after the lengths, if the cache has it, in this
# order: window rings, recurrent and conv states, index keys (a
# decoder-hybrid-decoder has rings AND states).  The leaves a prefill
# replaces wholesale (``insert_states``), and the axis of each that counts
# the slots.
_CARRIED = ("wk", "wv", "ssm", "conv", "ik")
_SLOT_AXIS = {"ssm": 1, "conv": 2}


def _carried_names(cache):
    """The leaves of ``cache`` that ride the carry after the lengths."""
    return tuple(name for name in _CARRIED if name in cache)


def _state_names(cache):
    """The state leaves of ``cache``: what ``insert_states`` replaces."""
    return tuple(name for name in _SLOT_AXIS if name in cache)


def _carry(cache, tok, lens):
    """A cache tree as ``decode_step``'s carry: K, V, the tokens, the
    lengths, then the states or the window rings if the model has
    them."""
    if "latent" in cache:       # the one leaf, where K lies; no V
        return (cache["latent"], None, tok, lens)
    # (a model without an attending layer: neither)
    return (cache.get("k"), cache.get("v"), tok, lens,
            *(cache[name] for name in _carried_names(cache)))


def _uncarry(carry, cache):
    """The carry as a cache tree with ``cache``'s leaves."""
    ck, cv, tok, lens, *state = carry
    if "latent" in cache:
        return {"latent": ck}, tok, lens
    kv = {} if ck is None else {"k": ck, "v": cv}
    return {**kv, **dict(zip(_carried_names(cache), state))}, tok, lens


# ------------------------------------------------------------- dense plane
@jax.named_scope("kv_write")
def _insert_rows(pool, new, slots):
    """A prefill group's rows into a pool, from each slot's first position
    on, every row written where it lies.  pool ``(layers, B, positions,
    Hkv, D)`` or its rows ``(layers, B, positions * Hkv, D)``; new
    ``(layers, G, P, Hkv, D)``, ``P`` at most the pool's positions; slots
    (G,), a negative one (the padding of a rung) writes nothing.  The pool
    is never reshaped: its two layouts tile differently on the chip, and a
    view of one as the other copies the whole pool in and out."""
    layers, G = new.shape[:2]
    # the group as the pool is stored: by position, or as rows
    return _insert_slices(
        pool, new.reshape((layers, G, -1) + pool.shape[3:]), slots)


def _insert_slices(pool, new, slots):
    """``new`` ``(layers, G, ...)`` into a pool ``(layers, B, ...)`` of the
    same rank, member g at slot ``slots[g]`` from the first index of every
    further axis on (index keys ``(layers, G, Di, P)`` lie as their pool
    does, positions last).  Per member one slot's slice is read, selected
    against the slot's sign and written back: nothing of the pool's shape,
    nor of ``(layers, B, P, ...)``, is made whatever ``G`` is, which is what
    a dense engine's slot count rests on (PERF.md section 4;
    ``tests/test_prefill_inplace.py`` holds it at the real widths)."""
    layers, G = new.shape[:2]
    new = new.astype(pool.dtype)
    one_slot = (layers, 1) + new.shape[2:]
    for g in range(G):
        at = (0, jnp.maximum(slots[g], 0)) + (0,) * (pool.ndim - 2)
        held = jax.lax.dynamic_slice(pool, at, one_slot)
        # A member is cut out as a slice: indexed out of ``(layers, G, 1,
        # ...)`` the v5e compiler lays a 5-D K out slot-major and copies
        # the whole pool into that layout and back (AOT, PR 33).
        pool = jax.lax.dynamic_update_slice(
            pool, jnp.where(slots[g] >= 0, new[:, g:g + 1], held), at)
    return pool


@jax.named_scope("kv_write")
def _ring_rows(rows, lengths, ring: int):
    """What a ring of ``ring`` positions holds once a prompt is in: of
    rows ``(layers, G, P, Hkv, D)`` by position, per ring row r the LAST
    position below the prompt's length that is r mod ``ring`` (ring rows
    no position has reached yet take what lies at r).  A prompt bucket
    the ring holds whole is written as it is."""
    P = rows.shape[2]
    if P <= ring:
        return rows
    r = jnp.arange(ring, dtype=jnp.int32)[None, :]
    laps = jnp.maximum((lengths[:, None] - 1 - r) // ring, 0)
    return jnp.take_along_axis(
        rows, (r + ring * laps)[None, :, :, None, None], axis=2)


def build_prefill(cfg: LlamaConfig) -> Callable:
    def prefill(params, cache, tokens, lengths, slots):
        last_logits, ks, vs, rows, states, window, index_keys = \
            llama.prefill_with_states(params, tokens, lengths, cfg)
        if index_keys is not None:
            with jax.named_scope("kv_write"):
                ik = _insert_slices(cache["ik"], index_keys, slots)
            cache = {"k": _insert_rows(cache["k"], ks, slots),
                     "v": _insert_rows(cache["v"], vs, slots), "ik": ik}
        elif cfg.kv_lora_rank:
            cache = {"latent": _insert_rows(cache["latent"], ks, slots)}
        elif window is not None:
            ring = cache["wk"].shape[2] // cfg.kv_row_heads
            cache = {
                **cache,
                "k": _insert_rows(cache["k"], ks, slots),
                "v": _insert_rows(cache["v"], vs, slots),
                "wk": _insert_rows(
                    cache["wk"], _ring_rows(window[0], lengths, ring), slots),
                "wv": _insert_rows(
                    cache["wv"], _ring_rows(window[1], lengths, ring), slots)}
        elif ks is not None:
            cache = {**cache, "k": _insert_rows(cache["k"], ks, slots),
                     "v": _insert_rows(cache["v"], vs, slots)}
        if states is not None:
            cache = insert_states(cache, states, slots)
        with jax.named_scope("sample"):
            first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        return cache, first, _expert_load(rows, bool(cfg.moe_held))

    return jax.jit(prefill, donate_argnums=(1,))


def build_decode_k(cfg: LlamaConfig) -> Callable:
    def decode_k(params, cache, tok_dev, len_dev,
                 ov_tok, ov_len, ov_mask, active, k, s_active):
        # ``sample``: which token and length a slot goes on from, and the
        # token loop's own collecting of the k steps' tokens
        with jax.named_scope("sample"):
            tok = jnp.where(ov_mask, ov_tok, tok_dev)
            lens = jnp.where(ov_mask, ov_len, len_dev)
            step = decode_step(cfg, params, s_active, active)
            carry, (toks, rows) = jax.lax.scan(
                step, _carry(cache, tok, lens), None, length=k)
        cache, tok, lens = _uncarry(carry, cache)
        return cache, toks, tok, lens, _expert_load(rows,
                                                    bool(cfg.moe_held))

    # tok_dev/len_dev (args 2, 3) are always overwritten by the
    # returned carries at every call site: donate them too.
    return jax.jit(decode_k, donate_argnums=(1, 2, 3),
                   static_argnames=("k", "s_active"))


def build_seat() -> Callable:
    """A prefill group's rows into the decode programs' carries (both
    planes' programs take ``tok_dev, len_dev`` first): ``first`` (G,), the group's
    first tokens as its prefill returned them, and ``lens`` (G,), the
    rows' lengths, at ``slots`` (G,).  A negative slot (the padding of a
    rung, a row that is not to decode) goes out of range and is dropped:
    left negative it would wrap to the last slot."""
    def seat(tok_dev, len_dev, first, lens, slots):
        with jax.named_scope("sample"):
            at = jnp.where(slots >= 0, slots, tok_dev.shape[0])
            return (tok_dev.at[at].set(first, mode="drop"),
                    len_dev.at[at].set(lens, mode="drop"))

    return jax.jit(seat, donate_argnums=(0, 1))


# ------------------------------------------------------------- paged plane
class BlockPool:
    """The layout of a pool of ``block_size``-token blocks stored as
    ``kv_quant`` says (``llama.init_paged_kv_cache``), and its reads and
    writes as the paged programs trace them.  Block tables pad with an
    out-of-range index: gathers clip (garbage, masked), scatters drop
    (no write)."""

    def __init__(self, cfg: LlamaConfig, block_size: int,
                 kv_quant: Optional[str]):
        from ray_tpu.serve.kv_cache import kv_quant_info

        self.cfg, self.bs = cfg, block_size
        self.fmt = kv_quant_info(kv_quant)

    def gather(self, pool, name, bt):
        """Gathered compute-dtype blocks (L, B, nb*bs, Hkv, D);
        quantized pools dequantize here (stored * per-block-head
        scale), so everything downstream of the gather is
        plane-agnostic."""
        N, L, bs, Hkv, D = pool[name].shape
        B, nb = bt.shape
        g = jnp.take(pool[name], bt.reshape(-1), axis=0, mode="clip")
        g = g.reshape(B, nb, L, bs, Hkv, D)
        g = g.transpose(2, 0, 1, 3, 4, 5).reshape(L, B, nb * bs, Hkv, D)
        if self.fmt is None:
            return g
        s = jnp.take(pool[name + "_scale"], bt.reshape(-1), axis=0,
                     mode="clip")               # (B*nb, L, bs, Hkv)
        s = s.reshape(B, nb, L, bs, Hkv).transpose(
            2, 0, 1, 3, 4).reshape(L, B, nb * bs, Hkv)
        return llama.dequantize_kv_blocks(g, s, self.cfg.dtype)

    def set_blocks(self, pool, name, flat, updates):
        """Store block updates ((M, L, bs, Hkv, D), compute dtype)
        at ``flat`` indices; quantized pools quantize on the way in
        (scale written next to the block)."""
        if self.fmt is None:
            return {name: pool[name].at[flat].set(
                updates.astype(pool[name].dtype), mode="drop")}
        q, sc = llama.quantize_kv_blocks(
            updates, self.fmt.qmax, jnp.dtype(self.fmt.dtype_name))
        return {
            name: pool[name].at[flat].set(q, mode="drop"),
            name + "_scale": pool[name + "_scale"].at[flat].set(
                sc, mode="drop"),
        }

    @jax.named_scope("kv_write")
    def store(self, pool, flat, kb, vb):
        """The pool with K and V blocks ``flat`` replaced."""
        return {**pool, **self.set_blocks(pool, "k", flat, kb),
                **self.set_blocks(pool, "v", flat, vb)}

    @jax.named_scope("kv_write")
    def scatter(self, pool, bt, ck, cv):
        """K/V in the gathered layout (``gather``'s; a prefill's rows
        (L, G, P, Hkv, D) are that layout with the last block to pad
        out) written back as whole BLOCKS (block-granular indices, the
        layout XLA handles well)."""
        B, nb = bt.shape

        def blocks(g):
            L, _, S, Hkv, D = g.shape
            if S != nb * self.bs:
                g = jnp.pad(g, ((0, 0), (0, 0), (0, nb * self.bs - S),
                                (0, 0), (0, 0)))
            u = g.reshape(L, B, nb, self.bs, Hkv, D)
            return u.transpose(1, 2, 0, 3, 4, 5).reshape(
                B * nb, L, self.bs, Hkv, D)

        return self.store(pool, bt.reshape(-1), blocks(ck), blocks(cv))

    def extract(self, pool, idx):
        """``inject``'s inverse: blocks ``idx`` of the pool, K and V,
        each (n, L, bs, Hkv, D) at FULL PRECISION, so that a quantized
        prefill replica can feed a bf16 decode replica (and vice
        versa); the ingest side requantizes on inject.  Gathered ON
        DEVICE — materializing the whole pool to host would move the
        full pool bytes per request on a real accelerator."""
        def blocks(name):
            stored = jnp.take(pool[name], idx, axis=0)
            if self.fmt is None:
                return stored
            return llama.dequantize_kv_blocks(
                stored, jnp.take(pool[name + "_scale"], idx, axis=0),
                self.cfg.dtype)

        return blocks("k"), blocks("v")


def build_prefill_cold(blocks: BlockPool) -> Callable:
    cfg = blocks.cfg

    def prefill_cold(params, pool, tokens, lengths, write_bt):
        # Same computation as the dense plane's prefill (bit-equal
        # first tokens + K/V rows); only the insert differs.
        last_logits, ks, vs, rows = llama.prefill_forward(
            params, tokens, lengths, cfg, return_expert_rows=True)
        pool = blocks.scatter(pool, write_bt, ks, vs)
        with jax.named_scope("sample"):
            first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        return pool, first, _expert_load(rows)

    return jax.jit(prefill_cold, donate_argnums=(1,))


def build_prefill_warm(blocks: BlockPool) -> Callable:
    cfg = blocks.cfg

    def prefill_warm(params, pool, tokens, lengths, pos0,
                     prefix_bt, write_bt):
        # Prefix-cache hit: the SUFFIX attends the gathered shared
        # blocks plus itself — the shared prefix is never
        # recomputed (the whole point of COW prefix sharing).
        G, P = tokens.shape
        Sp = prefix_bt.shape[1] * blocks.bs
        suffix = jnp.arange(P, dtype=jnp.int32)[None, :]
        positions = pos0[:, None] + suffix
        prefix_pos = jnp.arange(Sp, dtype=jnp.int32)
        key_abs = jnp.concatenate(
            [jnp.broadcast_to(prefix_pos[None, :], (G, Sp)),
             positions], axis=1)
        key_valid = jnp.concatenate(
            [prefix_pos[None, :] < pos0[:, None],
             jnp.ones((G, P), bool)], axis=1)
        scale = cfg.attn_scale

        def kv_step(q, k, v, positions, prefix_l):
            ckp_l, cvp_l = prefix_l
            keys = jnp.concatenate(
                [ckp_l, k.astype(ckp_l.dtype)], axis=1)
            vals = jnp.concatenate(
                [cvp_l, v.astype(cvp_l.dtype)], axis=1)
            attn = llama._cache_attend(q, keys, vals, positions, scale,
                                       key_abs, key_valid)
            return attn, (k, v)

        last_logits, (ks, vs), rows, _states, _window = llama.layer_walk(
            params, tokens, cfg, kv_step, positions=positions,
            kv_layers=(blocks.gather(pool, "k", prefix_bt),
                       blocks.gather(pool, "v", prefix_bt)),
            valid=suffix < lengths[:, None], lengths=lengths)
        with jax.named_scope("sample"):
            first = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
        pool = blocks.scatter(pool, write_bt, ks, vs)
        return pool, first, _expert_load(rows)

    return jax.jit(prefill_warm, donate_argnums=(1,))


def build_decode_paged(blocks: BlockPool) -> Callable:
    cfg = blocks.cfg

    def decode_paged(params, pool, tok_dev, len_dev, ov_tok,
                     ov_len, ov_mask, active, bt, k):
        with jax.named_scope("sample"):
            tok = jnp.where(ov_mask, ov_tok, tok_dev)
            lens = jnp.where(ov_mask, ov_len, len_dev)
        ck = blocks.gather(pool, "k", bt)
        cv = blocks.gather(pool, "v", bt)
        step = decode_step(cfg, params, bt.shape[1] * blocks.bs, active)
        with jax.named_scope("sample"):
            (ck, cv, tok, lens), (toks, rows) = jax.lax.scan(
                step, (ck, cv, tok, lens), None, length=k)
        pool = blocks.scatter(pool, bt, ck, cv)
        return pool, toks, tok, lens, _expert_load(rows)

    # tok_dev/len_dev (args 2, 3) are always overwritten by the
    # returned carries at every call site: donate them too.
    return jax.jit(decode_paged, donate_argnums=(1, 2, 3),
                   static_argnames=("k",))


def build_inject(blocks: BlockPool) -> Callable:

    def inject(pool, kb, vb, dest):
        # Handoff blocks arrive FULL PRECISION (the prefill side
        # dequantizes on extract), so quantized and bf16 engines
        # interoperate across a disaggregated pair.
        return blocks.store(pool, dest, kb, vb)

    return jax.jit(inject, donate_argnums=(0,))


def build_spec_verify(blocks: BlockPool) -> Callable:
    cfg = blocks.cfg

    def spec_verify(params, pool, tokens, positions, active, bt):
        """Target-model verification of a draft proposal: T tokens
        per slot in ONE pass over the gathered block layout.
        tokens/positions: (B, T) — [last accepted, d1..d_{T-1}] at
        absolute positions; returns the target's greedy token for
        positions+1 (B, T) and writes the inputs' K/V at their
        positions (gathered index == absolute position, same
        invariant as the decode step — which is what keeps spec
        output bit-identical to plain greedy decode)."""
        S = bt.shape[1] * blocks.bs
        key_pos = jnp.arange(S, dtype=jnp.int32)
        onehot = ((key_pos[None, None, :]
                   == positions[:, :, None])
                  & active[:, None, None])            # (B, T, S)
        written = onehot.any(axis=1)[:, :, None, None]
        proj = onehot.astype(cfg.dtype)
        scale = cfg.attn_scale

        def kv_step(q, kk, vv, positions, cache_l):
            ck_l, cv_l = cache_l
            # One-hot projection places the T fresh rows at their
            # absolute positions (scatters would serialize on TPU).
            with jax.named_scope("kv_write"):
                up_k = jnp.einsum("bts,bthd->bshd", proj, kk)
                up_v = jnp.einsum("bts,bthd->bshd", proj, vv)
                ck_l = jnp.where(written, up_k.astype(ck_l.dtype),
                                 ck_l)
                cv_l = jnp.where(written, up_v.astype(cv_l.dtype),
                                 cv_l)
            attn = llama._cache_attend(q, ck_l, cv_l, positions,
                                       scale)
            return attn, (ck_l, cv_l)

        logits, (ck, cv), _rows, _states, _window = llama.layer_walk(
            params, tokens, cfg, kv_step, positions=positions,
            kv_layers=(blocks.gather(pool, "k", bt),
                       blocks.gather(pool, "v", bt)),
            valid=active[:, None])
        with jax.named_scope("sample"):
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return blocks.scatter(pool, bt, ck, cv), toks

    return jax.jit(spec_verify, donate_argnums=(1,))


# ------------------------------------------------------- draft plane (spec)
# The speculative draft keeps a DENSE per-slot cache (the draft is small —
# paging it buys nothing) and rides the SAME decode step as the dense
# plane, its cache that step's carry with the rows written in place, so its
# cache bookkeeping inherits the write-before-attend invariant.
def truncated_draft(cfg: LlamaConfig, params, n: int):
    """Layer-truncated self-draft ``(config, params)``: the target's
    first ``n`` layers + its own norm/head.  Zero extra weights, and the
    shared residual stream keeps draft/target argmaxes correlated even
    for untrained params (the accept-rate floor the spec tests rely
    on)."""
    return (dataclasses.replace(cfg, n_layers=n),
            {**params, "layers": jax.tree.map(lambda x: x[:n],
                                              params["layers"])})


def build_draft_prefill(dcfg: LlamaConfig) -> Callable:
    def draft_prefill(params, cache, tokens, lengths, slots):
        _logits, ks, vs = llama.prefill_forward(params, tokens,
                                                lengths, dcfg)
        return {"k": _insert_rows(cache["k"], ks, slots),
                "v": _insert_rows(cache["v"], vs, slots)}

    return jax.jit(draft_prefill, donate_argnums=(1,))


def build_draft_propose(dcfg: LlamaConfig) -> Callable:
    def draft_propose(params, cache, tok, pos, active, k, s_active):
        step = decode_step(dcfg, params, s_active, active)
        with jax.named_scope("sample"):
            (ck, cv, tok, pos), (toks, _rows) = jax.lax.scan(
                step, (cache["k"], cache["v"], tok, pos), None,
                length=k)
        return {"k": ck, "v": cv}, toks

    return jax.jit(draft_propose, donate_argnums=(1,),
                   static_argnames=("k", "s_active"))

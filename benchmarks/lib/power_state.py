"""What a power-retention engine holds of a request: every layer's state a
key/value head, read back from the ENGINE'S OWN cache.

The tokens a request emits cannot tell a state kept or advanced in bfloat16
from one in float32 (a bfloat16 stream's rounding moves a logit further), and
the configuration states float32 (``dtype.power_state``).  So the comparison
that decides ``correct`` (``references/brumby_decoder.teacher_forced_gap``)
also reads the STATE.  PR 61's form (``lib/nemotron_state.py``) replays a
checked request through fresh programs into a second cache of the engine's
geometry; a second cache of this model does not fit beside the first (277 MB
a slot, 4.4 GB at 16 slots), so nothing is built here: ``served_states``
takes the request once more through the LIVE engine's own compiled programs
(``LLMServer._prefill``, ``._decode_k`` at its own ``decode_chunk``) into a
slot of its own cache, every other slot advancing beside it on whatever its
last tenant left (the kernel walks all of the engine's slots, as in the
timed window), and hands back that slot's states of EVERY layer in the
reference's own order (``brumby_decoder.triangle``), whatever layout the
program keeps, with the tokens the slot emitted on the way: a whole chunk
feeds on its own tokens, so the reference sums the state over THOSE
(``brumby_decoder.teacher_forced_gap`` says what it does when they part from
the reply's).  ``deviation`` is the distance, a layer over its whole state
and over each head.

Nothing here is timed: it runs after the window and the drain, on the idle
engine (``live_engine`` refuses a busy one), and adds no device memory but a
slot's states on their way to the host.
"""

from __future__ import annotations

import gc
from typing import Any, Dict, Sequence

import numpy as np


def live_engine(params):
    """The running, idle ``LLMServer`` of this process that serves
    ``params``.  The harness hands a reference the weights and a reply, not
    the engine that made it (``kinds/serve_llm.check_against_reference``);
    the engine is an object of this process all the same, under
    ``serve.run`` too, and holds the very arrays it was given."""
    from ray_tpu.serve.llm import LLMServer

    found = [o for o in gc.get_objects() if isinstance(o, LLMServer)
             and hasattr(o, "_thread") and not o._stop.is_set()
             and o.cfg.layers_of("power")]
    if len(found) > 1:
        found = [o for o in found
                 if o.params["lm_head"] is params["lm_head"]]
    if len(found) != 1:
        raise RuntimeError(
            f"the state check reads the live engine's own cache: "
            f"{len(found)} running power-retention LLMServer objects of "
            f"these weights in this process")
    server = found[0]
    if any(r is not None for r in server.slot_req) or server._backlog \
            or not server._queue.empty():
        raise RuntimeError("the state check needs an idle engine")
    return server


def to_triangle(state: np.ndarray):
    """Stored states ``(..., Hkv, d/2 + 2, d, d)`` (``ops/
    power_state_update.py``: tile ``s`` holds the products of channels ``a``
    and ``a - s``, values on the rows) as ``(S (..., Hkv, D, d), z (...,
    Hkv, D))`` in ``brumby_decoder.triangle``'s order and weights."""
    from benchmarks.references.brumby_decoder import triangle

    d = state.shape[-1]
    a, b, _w = triangle(d)
    s = b - a
    tile = np.where(s <= d // 2, s, d - s)
    lane = np.where(s <= d // 2, b, a)
    # a pair half the width apart lies twice in its tile, each at weight 1
    factor = np.where(tile == d // 2, np.sqrt(2.0), 1.0)
    # (indices apart: numpy puts their axis first)
    S = np.moveaxis(state[..., :-1, :, :][..., tile, :, lane], 0, -2)
    z = state[..., -1, :, :][..., tile, lane]
    return S * factor[:, None], z * factor


def served_states(params, prompt: Sequence[int], emitted: Sequence[int]):
    """``prompt`` through the own prefill program of ``live_engine(params)``
    (its own bucket for that length, one row) into one of its slots, then whole
    chunks of its own decode program, every slot advancing, as many as the
    reply has whole chunks behind its first token.  -> ``{"tokens": what the
    slot emitted (the prefill's first token, then a chunk's), "positions":
    how many positions its states have taken in (the prompt and all of
    ``tokens`` but the last), "S", "z": ``to_triangle`` of its states of
    every layer, "slot", "slots", "k"}``."""
    import jax.numpy as jnp

    server = live_engine(params)
    slots, k = server.max_slots, server.decode_chunk
    # (another slot a request: the walk over slots is read at several)
    slot = (len(prompt) + len(emitted)) % slots
    bucket = min(b for b in server.buckets if b >= len(prompt))
    row = np.zeros((1, bucket), np.int32)
    row[0, :len(prompt)] = prompt
    server.cache, first, _load = server._prefill(
        server.params, server.cache, jnp.asarray(row),
        jnp.asarray([len(prompt)], jnp.int32),
        jnp.asarray([slot], jnp.int32))
    tokens = [int(np.asarray(first)[0])]
    # every slot goes on from the request's length: the others on the
    # states their last tenants left (a RoPE position is all a length is)
    tok = np.zeros(slots, np.int32)
    tok[slot] = tokens[0]
    over = [jnp.asarray(tok), jnp.full(slots, len(prompt), jnp.int32),
            jnp.ones(slots, bool)]
    everyone = jnp.ones(slots, bool)
    # (two buffers: the program donates both)
    tok_dev, len_dev = (jnp.zeros(slots, jnp.int32) for _ in range(2))
    for _ in range((len(emitted) - 1) // k):
        server.cache, toks, tok_dev, len_dev, _load = server._decode_k(
            server.params, server.cache, tok_dev, len_dev, *over, everyone,
            k=int(k), s_active=int(server.decode_buckets[-1]))
        over = [jnp.zeros(slots, jnp.int32), jnp.zeros(slots, jnp.int32),
                jnp.zeros(slots, bool)]
        tokens += [int(t) for t in np.asarray(toks)[:, slot]]
    stored = np.asarray(server.cache["ssm"][:, slot].astype(jnp.float32))
    S, z = to_triangle(stored)
    return {"tokens": tokens, "positions": len(prompt) + len(tokens) - 1,
            "S": S, "z": z, "slot": slot, "slots": slots, "k": k}


def deviation(S: np.ndarray, z: np.ndarray, S_ref: np.ndarray,
              z_ref: np.ndarray) -> Dict[str, Any]:
    """``|served - reference| / |reference|`` (Frobenius) of every layer's
    states ``S (L, Hkv, D, d)`` with their normalisers ``z (L, Hkv, D)``:
    ``whole``, a layer over all its heads, and ``head``, a layer's heads
    each by itself.  A sound engine's difference is its bfloat16 stream's
    rounding of what ENTERS the state; a state rounded as it is kept, or
    advanced in less than float32, adds a rounding a STEP, which a slow
    head (a decay near 1: a sum over thousands of steps) gathers and a fast
    head forgets -- so the furthest head tells the two apart."""
    def flat(S, z):
        return np.concatenate(
            [S.astype(np.float64).reshape(S.shape[:2] + (-1,)),
             z.astype(np.float64)], axis=-1)

    got, want = flat(S, z), flat(S_ref, z_ref)
    heads = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    whole = np.linalg.norm(got - want, axis=(1, 2)) \
        / np.linalg.norm(want, axis=(1, 2))
    return {"whole": [float(x) for x in whole],
            "head": [[float(x) for x in layer] for layer in heads]}

"""What a Nemotron-H engine holds of a request after it has served it: the
recurrent state of every Mamba-2 block, read back from the slot.

The tokens a request emits cannot tell a state kept in bfloat16 from one kept
in float32 (a bfloat16 stream's rounding moves a logit ten times as far:
PERF.md section 6, PR 61), and the configuration states float32
(``dtype.ssm_state``).  So the comparison that decides ``correct``
(``references/nemotron_h_decoder.teacher_forced_gap``) also reads the STATE:
``served_states`` takes a checked request once more through the programs the
engine is built from -- ``llama_serve.build_prefill`` into a slot of a fresh
cache of the engine's own geometry (the timed slots x ``max_len``, the
request's prefill bucket), then ``llama_serve.build_decode_k`` one token a
call with the token the engine EMITTED put over the program's own
(``ov_tok``: teacher forcing through the program's own seam), the other slots
idle -- and hands back the slot's states as the cache stores them.  The
reference's recurrence gives the same states in float32
(``teacher_forced_report``'s ``states``); ``deviation`` is their distance,
over a block's whole state and over its furthest head.

Nothing here is timed: it runs after the window and the drain, beside the
idle engine, which keeps its weights (shared) and its own cache.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Sequence

import numpy as np


def geometry(config: Dict[str, Any]) -> Dict[str, Any]:
    """``state_check`` of the configuration's file: the slots and prefill
    buckets of the cell's engine (``benchmarks/tests/test_nemotron_cell.py``
    holds them to the cell's file)."""
    check = config["state_check"]
    return {"slots": int(check["slots"]),
            "buckets": tuple(int(b) for b in check["prefill_buckets"])}


@functools.lru_cache(maxsize=2)
def programs(cfg):
    """``(prefill, decode_k)`` of ``cfg``, traced once for a run's checked
    requests.  (A tool that patches the program under one ``cfg`` clears
    this between its variants.)"""
    from ray_tpu.models import llama_serve

    return llama_serve.build_prefill(cfg), llama_serve.build_decode_k(cfg)


def served_states(cfg, params, prompt: Sequence[int], emitted: Sequence[int],
                  slots: int, max_len: int, buckets: Sequence[int]):
    """The states ``(Lm, nh, hd, N)`` float32 (numpy) that slot ``slots - 1``
    holds after ``prompt`` was prefilled into it and every emitted token but
    the last was decoded: what the engine held when it emitted the last."""
    import jax.numpy as jnp

    from ray_tpu.models import llama_serve

    prefill, decode_k = programs(cfg)
    slot = slots - 1
    bucket = min(b for b in buckets if b >= len(prompt))
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :len(prompt)] = prompt
    cache, _first, _load = prefill(
        params, llama_serve.init_cache(cfg, slots, max_len),
        jnp.asarray(tokens), jnp.asarray([len(prompt)], jnp.int32),
        jnp.asarray([slot], jnp.int32))
    mine = np.zeros(slots, bool)
    mine[slot] = True
    mine = jnp.asarray(mine)
    # (two buffers: the program donates both)
    tok, lens = (jnp.zeros(slots, jnp.int32) for _ in range(2))
    for t, token in enumerate(emitted[:-1]):
        # (a fresh array a step: a dispatch may still read the last one)
        cache, _toks, tok, lens, _load = decode_k(
            params, cache, tok, lens, jnp.full(slots, token, jnp.int32),
            jnp.full(slots, len(prompt) + t, jnp.int32), mine, mine,
            k=1, s_active=max_len)
    nh, hd = cfg.ssm_heads, cfg.ssm_head_dim
    stored = np.asarray(cache["ssm"][:, slot].astype(jnp.float32))
    del cache
    # the cache keeps a state as (N, nh x hd): the channels on the lanes
    return stored.reshape(stored.shape[0], -1, nh, hd).transpose(0, 2, 3, 1)


def deviation(served: np.ndarray, reference: np.ndarray) -> Dict[str, list]:
    """Per Mamba-2 block, ``|served - reference| / |reference|`` (Frobenius):
    ``whole``, over the block's whole state, and ``head``, the largest over
    its heads, each head's (hd, N) state by itself.  A sound engine's
    difference is its bfloat16 stream's rounding of what ENTERS the state,
    the same share of every head's; a state rounded as it is kept, or a
    recurrence run in less than float32, adds a rounding a STEP, which a
    slow head (a decay near 1: a sum over hundreds of steps) gathers and a
    fast head forgets -- so the largest head tells the two apart where the
    whole state, mostly fast heads, hardly does."""
    diff = (served.astype(np.float64) - reference).reshape(
        served.shape[:2] + (-1,))
    size = reference.astype(np.float64).reshape(diff.shape)
    heads = np.linalg.norm(diff, axis=-1) / np.linalg.norm(size, axis=-1)
    whole = np.linalg.norm(diff, axis=(1, 2)) / np.linalg.norm(
        size, axis=(1, 2))
    return {"whole": [float(x) for x in whole],
            "head": [float(x) for x in heads.max(-1)]}

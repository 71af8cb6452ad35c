"""Hold a configuration with a learned sparse-attention indexer to its
reference at the PUBLISHED widths, outside any timed window, and say what
the comparison that decides ``correct`` makes of broken programs:

    python3 benchmarks/tools/dsa_check.py --config keye-vl-2.0-30b-a3b \\
        --seed 2147486400

One process, weights from ``--seed`` by the program's own initialiser, ONE
set of them for every variant and for the reference.  The bare programs
(``build_prefill`` / ``build_decode_k``, 2 slots) take one request of
``--prompt`` tokens (past ``topk``, so that every query of its tail and
every decode step SELECTS) through the slot ANOTHER request held before it
(prefilled and decoded a chunk there), leave it out of one chunk that the
other slot decodes alone, and decode ``--new-tokens`` through K/V and the
index keys; the reference reads the reply back in one full forward pass
(``teacher_forced_report``: logits, not tokens).  Per variant and request
one JSON line: the raw gaps' largest, how many positions read over the
margin beside how many the model's own second pass leaves undecided there
(``own_gap``), and whether the cell's check would pass
(``kinds/serve_llm.py`` LOGIT_MARGIN).  For ``intact`` also the OVERLAP of
the engine's selected sets with the reference's: per decode step and layer, how many of the keys
the engine attended the reference selected too (the engine scores in
bfloat16, the reference in float32: keys near the ``topk``-th place flip).

VARIANTS (``broken``): ``intact``; ``recent_keys``, the selection replaced
by the most recent ``topk`` keys; ``no_selection``, every key attended;
``stale_index_keys``, a reused slot keeps the index keys of the request
before (its prefill's are not written); ``score_before_write``, a decode
step's new index key scored before it is written (it scores as the zeros
that lie there); ``float8_weights``, the weights rounded to float8_e4m3's
three mantissa bits (the precision below the configuration's bfloat16).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks.lib import program, runtime, spec  # noqa: E402

LOGIT_MARGIN = 0.25     # kinds/serve_llm.py's
VARIANTS = ("intact", "recent_keys", "no_selection", "stale_index_keys",
            "score_before_write", "float8_weights")


def broken(variant: str, cfg, max_len: int):
    """``(the variant's config, a function that gives a context manager
    which patches the program for it, what it does to the weights)``: the
    same weights under a program that is wrong in one place."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import indexer, llama_serve

    vcfg = dataclasses.replace(cfg, index_topk=max_len) \
        if variant == "no_selection" else cfg
    select = indexer.select

    def recency(qi, keys_t, w):
        # a key's score is its position: the top-k are the most recent
        shape = qi.shape[:2] + keys_t.shape[-1:]
        return jnp.broadcast_to(
            jnp.arange(shape[-1], dtype=jnp.float32), shape)

    def keys_kept(pool, new, slots):
        # the index keys' pool keeps what the tenant before left
        return pool

    def new_key_unwritten(score, n_valid, k):
        at = jnp.arange(score.shape[1], dtype=jnp.int32)[None, :]
        return select(jnp.where(at == n_valid[:, None] - 1, 0.0, score),
                      n_valid, k)

    patch = {"recent_keys": (indexer, "scores", recency),
             "stale_index_keys": (llama_serve, "_insert_slices", keys_kept),
             "score_before_write": (indexer, "select", new_key_unwritten),
             }.get(variant)

    @contextlib.contextmanager
    def patched():
        if patch is None:
            yield
            return
        module, name, fn = patch
        was = getattr(module, name)
        setattr(module, name, fn)
        try:
            yield
        finally:
            setattr(module, name, was)

    def weights(params, donate=False):
        """The weights the variant serves with: as they are, or rounded to
        float8_e4m3's mantissa (in place where ``donate``: a second copy
        does not fit the chip)."""
        if variant != "float8_weights":
            return params
        # reduce_precision: a convert there and back is folded away
        return jax.jit(lambda p: jax.tree.map(
            lambda w: jax.lax.reduce_precision(w, 8, 3), p),
            donate_argnums=(0,) if donate else ())(params)

    return vcfg, patched, weights


@contextlib.contextmanager
def recorded_selection(slot: int, into: list):
    """While open, every ``indexer.select`` a decode program runs also
    hands the positions slot ``slot`` attends to the host, in program order
    (a step's layers in turn): appended to ``into``."""
    import jax
    import numpy as np

    from ray_tpu.models import indexer

    select = indexer.select

    def record(keep):
        into.append(np.flatnonzero(np.asarray(keep)))

    def recording(score, n_valid, k):
        keep = select(score, n_valid, k)
        jax.debug.callback(record, keep[slot], ordered=True)
        return keep

    indexer.select = recording
    try:
        yield
    finally:
        indexer.select = select


def serve_one(cfg, params, before, prompt, new_tokens, buckets, max_len,
              k=16, slots=2, slot=1):
    """One request through fresh ``build_prefill`` / ``build_decode_k``
    programs of ``cfg``, in a slot that ``before`` (a prompt) was
    prefilled into and decoded one chunk in, and that sits out one chunk
    which the other slot decodes alone: the tokens it emits."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama_serve

    prefill = llama_serve.build_prefill(cfg)
    decode_k = llama_serve.build_decode_k(cfg)
    zeros, no = jnp.zeros(slots, jnp.int32), jnp.zeros(slots, bool)
    other = (slot + 1) % slots

    def fill(cache, tokens, at):
        bucket = min(b for b in buckets if b >= len(tokens))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(tokens)] = tokens
        return prefill(params, cache, jnp.asarray(toks),
                       jnp.asarray([len(tokens)], jnp.int32),
                       jnp.asarray([at], jnp.int32))[:2]

    def chunk(cache, tok, lens, who):
        active = jnp.zeros(slots, bool).at[jnp.asarray(who)].set(True)
        return decode_k(params, cache, tok, lens, zeros, zeros, no, active,
                        k=k, s_active=max_len)[:4]

    cache = llama_serve.init_cache(cfg, slots, max_len)
    tok, lens = zeros, zeros
    # the tenant before, in both slots: prefilled and decoded a chunk
    for at in (slot, other):
        cache, first = fill(cache, before, at)
        tok, lens = tok.at[at].set(first[0]), lens.at[at].set(len(before))
    cache, _out, tok, lens = chunk(cache, tok, lens, [slot, other])
    # the request, into the reused slot; it sits out one chunk
    cache, first = fill(cache, prompt, slot)
    tok, lens = tok.at[slot].set(first[0]), lens.at[slot].set(len(prompt))
    cache, _out, tok, lens = chunk(cache, tok, lens, [other])
    emitted = [int(first[0])]
    while len(emitted) < new_tokens:
        cache, out, tok, lens = chunk(cache, tok, lens, [slot])
        emitted += [int(t) for t in np.asarray(out)[:, slot]]
    return emitted[:new_tokens]


def judge(reference, params, prompt, emitted, config, pad_to):
    """What the reference reads of a reply, and what the cell's check
    makes of it."""
    import numpy as np

    report = reference.teacher_forced_report(params, prompt, emitted, config,
                                             pad_to=pad_to)
    gap = reference.take_out_undecided(report["gap"], report["own_gap"])
    return {**reference.gap_counts(report["gap"], report["own_gap"]),
            "judged_max": float(np.max(gap)),
            "passes": bool(np.max(gap) <= LOGIT_MARGIN)}


def overlap(reference, params, prompt, emitted, config, recorded, layers,
            slot_steps):
    """The engine's selected sets (``recorded``: a decode step's layers in
    turn, the steps of the chunks the slot decoded) against the
    reference's at the same positions: per (step, layer) the keys of the
    engine's set that the reference's lacks."""
    import numpy as np

    seq = np.asarray(list(prompt) + list(emitted[:-1]), np.int32)
    want = reference.selected_keys(params, seq, config)      # (L, S, S)
    missing, sizes = [], []
    for i, chosen in enumerate(recorded[:slot_steps * layers]):
        step, layer = divmod(i, layers)
        t = len(prompt) + step
        if t >= len(seq):
            break
        missing.append(int((~want[layer, t, chosen]).sum()))
        sizes.append(len(chosen))
    by_layer = [float(np.mean(missing[layer::layers]))
                for layer in range(layers)] if missing else []
    return {"sets": len(missing), "set_size_max": max(sizes, default=0),
            "keys_not_in_reference_max": max(missing, default=0),
            "keys_not_in_reference_mean": float(np.mean(missing))
            if missing else 0.0,
            "keys_not_in_reference_mean_by_layer": by_layer,
            "sets_identical": int(sum(m == 0 for m in missing))}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--before", type=int, default=3000)
    ap.add_argument("--prompt", type=int, default=5000)
    ap.add_argument("--new-tokens", type=int, default=256)
    ap.add_argument("--buckets", default="4096,8192")
    ap.add_argument("--max-len", type=int, default=8192)
    ap.add_argument("--requests", type=int, default=1)
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--bench-dir", default=BENCH_DIR)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from ray_tpu.models import llama

    runtime.place_caches()
    with open(os.path.join(args.bench_dir, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    reference = spec.load_module("references", config["reference"],
                                 args.bench_dir)
    assert args.prompt + args.new_tokens <= args.max_len
    buckets = [int(b) for b in args.buckets.split(",")]
    cfg = program.llama_config(config, max_seq_len=args.max_len)
    init = jax.jit(lambda key: llama.init_params(key, cfg, cfg.dtype))
    params = init(jax.random.key(args.seed))
    slot, k = 1, 16
    out = {}
    for variant in args.variants.split(","):
        vcfg, patched, weights = broken(variant, cfg, args.max_len)
        requests = []
        for r in range(args.requests):
            rng = np.random.default_rng([args.seed, 3, r])
            requests.append(tuple(
                rng.integers(0, config["vocab_size"], n).astype(np.int32)
                for n in (args.before, args.prompt)))
        # every reply first, under the variant's weights (which take the
        # place of the sound ones on the device), then the reference
        served = weights(params, donate=True)
        replies, recorded = [], []
        with patched():
            for before, prompt in requests:
                sets = []
                record = recorded_selection(slot, sets) \
                    if variant == "intact" else contextlib.nullcontext()
                with record:
                    replies.append(serve_one(
                        vcfg, served, before, prompt, args.new_tokens,
                        buckets, args.max_len, k=k, slot=slot))
                    jax.effects_barrier()
                # the slot's own chunks come after the two it shares or
                # sits out: (1 + 1) chunks x k steps x layers sets
                recorded.append(sets[2 * k * cfg.n_layers:])
        if served is not params:
            del served
            params = init(jax.random.key(args.seed))
        for r, ((_before, prompt), emitted) in enumerate(
                zip(requests, replies)):
            got = judge(reference, params, prompt, emitted, config,
                        args.max_len)
            if variant == "intact":
                got["selection"] = overlap(
                    reference, params, prompt, emitted, config, recorded[r],
                    cfg.n_layers, args.new_tokens - 1)
            out[f"{variant}.{r}"] = got
            print(json.dumps({"event": "gaps", "variant": variant,
                              "request": r, **got}), flush=True)
    print(json.dumps({"event": "done", **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

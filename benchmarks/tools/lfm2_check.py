"""Hold a short-convolution + expert configuration's serving programs to
its reference at the PUBLISHED widths, outside any timed window, and say
what the comparison that decides ``correct`` makes of broken programs:

    python3 benchmarks/tools/lfm2_check.py --config lfm2-8b-a1b \\
        --seed 2147486400

One process, weights from ``--seed`` by the program's own initialiser,
ONE set of them for every variant and for the reference.  The bare
programs (``build_prefill`` / ``build_decode_k``, 2 slots) take one
request through the slot ANOTHER request held before it (prefilled and
decoded a chunk there), leave it out of one chunk that the other slot
decodes alone, and decode ``--new-tokens`` through K/V and the conv
states; the reference reads the reply back in one full forward pass
(``teacher_forced_report``: logits, not tokens).  Per variant one JSON
line: the raw gaps' counts, what the cell's check sees
(``teacher_forced_gap``) and whether it would pass (``kinds/serve_llm.py``
LOGIT_MARGIN).

VARIANTS (``broken``): the program ``intact``; the selection bias left
out of the choice; the bias left IN the gates; the conv's oldest tap
dropped; the conv state of the request before left in a reused slot; the
conv state advanced on a slot that is not in the launch; the q/k norm
over the whole projection instead of a head; the weights rounded to
float8_e4m3's three mantissa bits (the precision below the
configuration's bfloat16).  Each broken one has to be judged not correct.
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
VARIANTS = ("intact", "no_bias_in_choice", "bias_in_gates",
            "conv_tap_dropped", "stale_conv_state", "idle_slot_advanced",
            "qk_norm_whole", "float8_weights")


def broken(variant: str, cfg):
    """``(the variant's config, a function that gives a context manager
    which patches the program for it, what it does to the weights)``: the
    same weights under a program that is wrong in one place."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, llama_serve, moe, shortconv

    fields = {
        "no_bias_in_choice": {"moe_router_bias": False},
        "qk_norm_whole": {"qk_norm": True, "qk_head_norm": False},
    }.get(variant, {})
    vcfg = dataclasses.replace(cfg, **fields)
    route, taps, qkv = moe._route, shortconv._taps, llama._qkv_rope
    shortconv_decode = shortconv.decode

    def bias_in_gates(xt, router, k, norm_topk, groups=0, top_groups=0,
                      scale=1.0, score="softmax", bias=None):
        probs, _gates, chosen = route(xt, router, k, norm_topk, groups,
                                      top_groups, scale, score, bias)
        gates = jnp.take_along_axis(probs + bias, chosen, axis=-1)
        return probs, scale * gates / (
            gates.sum(-1, keepdims=True) + 1e-6), chosen

    def tap_dropped(window, layer):
        return taps(window[1:], {"conv_w": layer["conv_w"][1:]})

    def state_kept(cache, states, slots):
        return cache

    def always_advance(h, layer, c, conv, m, active):
        return shortconv_decode(h, layer, c, conv, m,
                                jnp.ones_like(active))

    def norm_whole(x, layer, sin, cos, config, kind="attention"):
        # the same weights, laid over the whole projection
        return qkv(x, {**layer,
                       "q_norm": jnp.tile(layer["q_norm"], config.n_heads),
                       "k_norm": jnp.tile(layer["k_norm"],
                                          config.n_kv_heads)},
                   sin, cos, config, kind)

    patch = {"bias_in_gates": (moe, "_route", bias_in_gates),
             "conv_tap_dropped": (shortconv, "_taps", tap_dropped),
             "stale_conv_state": (llama_serve, "insert_states", state_kept),
             "idle_slot_advanced": (shortconv, "decode", always_advance),
             "qk_norm_whole": (llama, "_qkv_rope", norm_whole),
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
        """The weights the variant serves with: as they are, or rounded
        to float8_e4m3's mantissa (in place where ``donate``: a second
        copy of 9.3 GB does not fit the chip)."""
        if variant != "float8_weights":
            return params
        # reduce_precision: a convert there and back is folded away
        return jax.jit(lambda p: jax.tree.map(
            lambda w: jax.lax.reduce_precision(w, 8, 3), p),
            donate_argnums=(0,) if donate else ())(params)

    return vcfg, patched, weights


def serve_one(cfg, params, before, prompt, new_tokens, bucket, max_len,
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

    raw = reference.teacher_forced_report(params, prompt, emitted, config,
                                          pad_to=pad_to)["gap"]
    gap = reference.take_out_swaps(raw)
    return {"counts": reference.gap_counts(raw),
            "judged_max": float(np.max(gap)),
            "passes": bool(np.max(gap) <= LOGIT_MARGIN)}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--before", type=int, default=100)
    ap.add_argument("--prompt", type=int, default=200)
    ap.add_argument("--new-tokens", type=int, default=256)
    ap.add_argument("--bucket", type=int, default=256)
    ap.add_argument("--max-len", type=int, default=512)
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
    cfg = program.llama_config(config, max_seq_len=args.max_len)
    init = jax.jit(lambda key: llama.init_params(key, cfg, cfg.dtype))
    params = init(jax.random.key(args.seed))
    out = {}
    for variant in args.variants.split(","):
        vcfg, patched, weights = broken(variant, cfg)
        requests = []
        for r in range(args.requests):
            rng = np.random.default_rng([args.seed, 3, r])
            requests.append(tuple(
                rng.integers(0, config["vocab_size"], n).astype(np.int32)
                for n in (args.before, args.prompt)))
        # every reply first, under the variant's weights (which take the
        # place of the sound ones on the device), then the reference
        served = weights(params, donate=True)
        with patched():
            replies = [serve_one(vcfg, served, before, prompt,
                                 args.new_tokens, args.bucket, args.max_len)
                       for before, prompt in requests]
        if served is not params:
            del served
            params = init(jax.random.key(args.seed))
        for r, ((_before, prompt), emitted) in enumerate(
                zip(requests, replies)):
            got = judge(reference, params, prompt, emitted, config,
                        args.max_len)
            out[f"{variant}.{r}"] = got
            print(json.dumps({"event": "gaps", "variant": variant,
                              "request": r, **got}), flush=True)
    print(json.dumps({"event": "done", **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

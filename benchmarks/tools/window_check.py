"""Hold a window/full-attention configuration's serving programs to its
reference at the PUBLISHED widths, outside any timed window, and say what
the comparison that decides ``correct`` makes of broken programs:

    python3 benchmarks/tools/window_check.py --config smallthinker-21b-a3b \\
        --seed 2147486200

One process, weights from ``--seed`` by the program's own initialiser.
The bare programs (``build_prefill`` / ``build_decode_k``, 2 slots, a
step a call so that the step's expert histogram IS the token's choice)
take one prompt that wraps the ring, decode ``--new-tokens`` more through
both pools, and the reference reads the reply back in one full forward
pass (``teacher_forced_report``: logits and routing, not tokens).  Per
variant one JSON line: the raw gaps, the positions where the engine's
top-k set differs from the reference's in some layer (``flipped``), the
largest gap where it does not, and ``judged``, what the cell's check sees
(``teacher_forced_gap``).  VARIANTS: the program intact; window layers
without RoPE; the router reading the stream after attention; the weights
rounded to float8_e4m3's three mantissa bits (the precision below the
configuration's bfloat16); a window one key too wide (Mosaic refuses a
ring of 4,097 positions x 4 kv heads: toy widths only).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks.lib import program, runtime, spec  # noqa: E402

VARIANTS = ("intact", "unroped_window", "router_after", "float8_weights",
            "window_off_by_one")


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt", type=int, default=4500)
    ap.add_argument("--new-tokens", type=int, default=512)
    ap.add_argument("--bucket", type=int, default=8192)
    ap.add_argument("--max-len", type=int, default=16384)
    ap.add_argument("--variants", default=",".join(VARIANTS[:4]))
    ap.add_argument("--bench-dir", default=BENCH_DIR)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama, llama_serve

    runtime.place_caches()
    with open(os.path.join(args.bench_dir, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    reference = spec.load_module("references", config["reference"],
                                 args.bench_dir)
    window = config["sliding_window_size"]
    assert args.prompt + args.new_tokens <= args.max_len
    prompt = np.random.default_rng([args.seed, 3]).integers(
        0, config["vocab_size"], args.prompt).astype(np.int32)
    cfg = program.llama_config(config, max_seq_len=args.max_len)
    init = jax.jit(lambda key: llama.init_params(key, cfg, cfg.dtype))
    # reduce_precision: a convert there and back is folded away
    float8 = jax.jit(lambda p: jax.tree.map(
        lambda w: jax.lax.reduce_precision(w, 8, 3), p), donate_argnums=0)
    broken = {
        "window_off_by_one": {"window_size": window + 1},
        "unroped_window": {"nope_kinds": ("attention", "window")},
        "router_after": {"moe_router_input": "ffn"},
    }
    SLOTS, slot = 2, 1
    toks = np.zeros((1, args.bucket), np.int32)
    toks[0, :args.prompt] = prompt
    out = {}
    for variant in args.variants.split(","):
        vcfg = program.llama_config(config, max_seq_len=args.max_len,
                                    **broken.get(variant, {}))
        params = init(jax.random.key(args.seed))
        if variant == "float8_weights":
            params = float8(params)
        cache = llama_serve.init_cache(vcfg, SLOTS, args.max_len)
        cache, first, _ = llama_serve.build_prefill(vcfg)(
            params, cache, jnp.asarray(toks),
            jnp.asarray([args.prompt], jnp.int32),
            jnp.asarray([slot], jnp.int32))
        decode_k = llama_serve.build_decode_k(vcfg)
        tok = jnp.zeros(SLOTS, jnp.int32).at[slot].set(first[0])
        lens = jnp.zeros(SLOTS, jnp.int32).at[slot].set(args.prompt)
        active = jnp.zeros(SLOTS, bool).at[slot].set(True)
        zeros, no = jnp.zeros(SLOTS, jnp.int32), jnp.zeros(SLOTS, bool)
        emitted, chose = [first[0]], []
        while len(emitted) < args.new_tokens:
            cache, step_toks, tok, lens, (rows, _) = decode_k(
                params, cache, tok, lens, zeros, zeros, no, active, k=1,
                s_active=args.max_len)
            emitted.append(step_toks[0, slot])
            chose.append(rows > 0)                       # (L, E)
        emitted = [int(t) for t in np.asarray(jnp.stack(emitted))]
        chose = np.asarray(jnp.stack(chose))             # (n - 1, L, E)
        del cache, params
        params = init(jax.random.key(args.seed))         # the reference's
        report = reference.teacher_forced_report(
            params, prompt, emitted, config,
            pad_to=args.prompt + args.new_tokens)
        judged = reference.take_out_swaps(report["gap"])
        del params
        # the first token is the prefill's: its routing is not handed out
        gap = report["gap"][1:]
        theirs = np.zeros(chose.shape, bool)
        np.put_along_axis(theirs, report["chosen"][:, 1:].transpose(1, 0, 2),
                          True, axis=-1)
        flipped = (chose != theirs).any(-1)              # (n - 1, L)
        out[variant] = {
            "gap_max": float(report["gap"].max()),
            "gap_mean": float(report["gap"].mean()),
            "over_swap_gap": int((report["gap"] > reference.SWAP_GAP).sum()),
            "swaps_allowed": reference.swaps_allowed(len(emitted)),
            "judged_max": float(judged.max()),
            "flipped_share": float(flipped.any(1).mean()),
            "flipped_by_layer": flipped.mean(0).round(4).tolist(),
            "gap_max_not_flipped": float(
                gap[~flipped.any(1)].max(initial=0.0))}
        print(json.dumps({"event": "gaps", "variant": variant,
                          **out[variant]}), flush=True)
    print(json.dumps({"event": "done", **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

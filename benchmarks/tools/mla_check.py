"""Hold a latent-attention configuration's serving programs to its
reference at the PUBLISHED widths, outside any timed window, and say what
the comparison that decides ``correct`` makes of broken programs:

    python3 benchmarks/tools/mla_check.py --config deepseek-v2 \\
        --seed 2147486200

One process, weights from ``--seed`` by the program's own initialiser,
ONE set of them for every variant and for the reference.  The bare
programs (``build_prefill`` / ``build_decode_k``, 2 slots) take one
prompt through the expanded prefill and decode ``--new-tokens`` more
through the latent cache and the absorbed kernel; the reference reads the
reply back in one full forward pass (``teacher_forced_gap``: logits, not
tokens).  Per variant one JSON line: the largest and mean gap and whether
the cell's check (``kinds/serve_llm.py`` LOGIT_MARGIN) would pass it.

VARIANTS (``broken``): the program ``intact``; the latent rows rounded to
float8_e4m3's three mantissa bits before they are kept (the precision
below the configuration's bfloat16); ``k_rope`` left out of the latent
row; the absorbed ``W_UK`` of the NEXT head; ``routed_scaling_factor``
dropped; the shared expert dropped; the group limit ignored; YaRN's factor
ignored.  Each broken one has to read over the margin.
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
VARIANTS = ("intact", "float8_latent", "no_k_rope", "other_head_w_uk",
            "no_routed_scale", "no_shared_expert", "no_group_limit",
            "no_yarn")


def broken(variant: str, cfg):
    """``(the variant's config, a context manager that patches the
    program for it)``: the same weights under a program that is wrong in
    one place."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    fields = {
        "no_routed_scale": {"moe_routed_scale": 1.0},
        "no_shared_expert": {"moe_shared_size": 0},
        "no_group_limit": {"moe_groups": 0, "moe_top_groups": 0},
        "no_yarn": {"rope_scaling": None if cfg.rope_scaling is None
                    else {**dict(cfg.rope_scaling), "factor": 1}},
    }.get(variant, {})
    vcfg = dataclasses.replace(cfg, **fields)
    down, absorb = llama.latent_down, llama.latent_absorb_query
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim

    def float8_latent(*a):
        cq, latent = down(*a)
        # reduce_precision: a convert there and back is folded away
        return cq, jax.lax.reduce_precision(latent, 4, 3)

    def no_k_rope(*a):
        cq, latent = down(*a)
        return cq, latent.at[..., rank:rank + rope].set(0)

    def other_head(q_nope, q_rope, layer, c):
        return absorb(q_nope, q_rope,
                      {**layer, "wk_b": jnp.roll(layer["wk_b"], 1, axis=0)},
                      c)

    patch = {"float8_latent": ("latent_down", float8_latent),
             "no_k_rope": ("latent_down", no_k_rope),
             "other_head_w_uk": ("latent_absorb_query", other_head)
             }.get(variant)

    @contextlib.contextmanager
    def patched():
        if patch is None:
            yield
            return
        name, fn = patch
        was = getattr(llama, name)
        setattr(llama, name, fn)
        try:
            yield
        finally:
            setattr(llama, name, was)

    return vcfg, patched()


def serve_one(cfg, params, prompt, new_tokens, bucket, max_len, k=16,
              slots=2, slot=1):
    """One request through fresh ``build_prefill`` / ``build_decode_k``
    programs of ``cfg``: the tokens it emits."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama_serve

    toks = np.zeros((1, bucket), np.int32)
    toks[0, :len(prompt)] = prompt
    cache = llama_serve.init_cache(cfg, slots, max_len)
    cache, first, _ = llama_serve.build_prefill(cfg)(
        params, cache, jnp.asarray(toks),
        jnp.asarray([len(prompt)], jnp.int32), jnp.asarray([slot], jnp.int32))
    decode_k = llama_serve.build_decode_k(cfg)
    tok = jnp.zeros(slots, jnp.int32).at[slot].set(first[0])
    lens = jnp.zeros(slots, jnp.int32).at[slot].set(len(prompt))
    active = jnp.zeros(slots, bool).at[slot].set(True)
    zeros, no = jnp.zeros(slots, jnp.int32), jnp.zeros(slots, bool)
    emitted = [int(first[0])]
    while len(emitted) < new_tokens:
        cache, out, tok, lens, _ = decode_k(
            params, cache, tok, lens, zeros, zeros, no, active, k=k,
            s_active=max_len)
        emitted += [int(t) for t in np.asarray(out)[:, slot]]
    return emitted[:new_tokens]


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt", type=int, default=4500)
    ap.add_argument("--new-tokens", type=int, default=128)
    ap.add_argument("--bucket", type=int, default=8192)
    ap.add_argument("--max-len", type=int, default=8192)
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
    prompt = np.random.default_rng([args.seed, 3]).integers(
        0, config["vocab_size"], args.prompt).astype(np.int32)
    cfg = program.llama_config(config, max_seq_len=args.max_len)
    params = jax.jit(lambda key: llama.init_params(key, cfg, cfg.dtype))(
        jax.random.key(args.seed))
    out = {}
    for variant in args.variants.split(","):
        vcfg, patched = broken(variant, cfg)
        with patched:
            emitted = serve_one(vcfg, params, prompt, args.new_tokens,
                                args.bucket, args.max_len)
        raw = reference.teacher_forced_report(
            params, prompt, emitted, config,
            pad_to=args.prompt + args.new_tokens)["gap"]
        gap = reference.take_out_swaps(raw)      # what the cell's check sees
        out[variant] = {"gap_max": float(raw.max()),
                        "gap_mean": float(raw.mean()),
                        "over_margin": int((raw > LOGIT_MARGIN).sum()),
                        "positions": len(raw),
                        "counts": reference.gap_counts(raw),
                        "judged_max": float(gap.max()),
                        "passes": bool(gap.max() <= LOGIT_MARGIN)}
        print(json.dumps({"event": "gaps", "variant": variant,
                          **out[variant]}), flush=True)
    print(json.dumps({"event": "done", **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

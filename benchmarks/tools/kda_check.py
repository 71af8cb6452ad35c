"""Hold a KDA + gated-attention + expert configuration's serving programs
to its reference at the PUBLISHED widths, outside any timed window, and say
what the comparison that decides ``correct`` makes of broken programs:

    python3 benchmarks/tools/kda_check.py --config solar-open2-250b \\
        --seed 2147486500

One process, weights from ``--seed`` by the program's own initialiser, ONE
set of them for every variant and for the reference.  The bare programs
(``build_prefill`` / ``build_decode_k``, 2 slots: ``lfm2_check.serve_one``)
take one request through the slot ANOTHER request held before it, leave it
out of one chunk that the other slot decodes alone, and decode
``--new-tokens`` through K/V, the matrix states and the conv tails; the
reference (the recurrence token by token, in blocks) reads the reply back in
one full forward pass (``teacher_forced_report``: logits, not tokens).  Per
variant and request one JSON line: the raw gaps' counts, what the cell's
check sees (``teacher_forced_gap``) and whether it would pass
(``kinds/serve_llm.py`` LOGIT_MARGIN).

VARIANTS (``broken``): the program ``intact``; the matrix state stored in
bfloat16; ``beta`` without its 2 (no negative eigenvalue); the decay
applied AFTER the rank-1 correction instead of before; the attending
layer's output gate dropped; the weights rounded to float8_e4m3's three
mantissa bits (the precision below the configuration's bfloat16).

``logit_distance`` is the CPU tests' reading of the same variants: the
program's own LOGITS, prefill and cached decode, against the reference's at
every position (``tests/test_solar_open2_serve.py``).
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
from benchmarks.tools.lfm2_check import judge, serve_one  # noqa: E402

VARIANTS = ("intact", "bf16_state", "beta_without_2",
            "decay_after_correction", "no_attention_gate", "float8_weights")


def _decay_after(q, k, v, g, b, state):
    """The delta rule with the decay on the wrong side of the correction,
    token by token: q, k, v, g (N, T, H, d), b (N, T, H)."""
    import jax
    import jax.numpy as jnp

    def step(S, x):
        q, k, v, g, b = x
        r = jnp.einsum("nhk,nhkv->nhv", k, S, precision="highest")
        S = S + b[..., None, None] * k[..., None] * (v - r)[..., None, :]
        S = jnp.exp(g)[..., None] * S
        return S, jnp.einsum("nhk,nhkv->nhv", q, S, precision="highest")

    state, o = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, b)))
    return jnp.moveaxis(o, 0, 1), state


def broken(variant: str, cfg):
    """``(the variant's config, a function that gives a context manager
    which patches the program for it)``: the same weights under a program
    that is wrong in one place."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import kda
    from ray_tpu.ops import kda_state_update as op

    fields = {"bf16_state": {"ssm_state_dtype": jnp.bfloat16},
              "no_attention_gate": {"attn_gate": False}}.get(variant, {})
    vcfg = dataclasses.replace(cfg, **fields)
    heads = kda._heads

    def beta_without_2(*args):
        q, k, v, g, b, z = heads(*args)
        return q, k, v, g, 0.5 * b, z

    def chunk_rule(q, k, v, g, b, state, chunk):
        return _decay_after(q, k, v, g, b, state)

    def state_update(ssm, layer, active, decay, q, k, v, b):
        held = jax.lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)
        o, new = _decay_after(q[:, None], k[:, None], v[:, None],
                              jnp.log(decay)[:, None], b[:, None],
                              held.astype(jnp.float32))
        new = jnp.where(active[:, None, None, None], new.astype(ssm.dtype),
                        held)
        return (jax.lax.dynamic_update_index_in_dim(ssm, new, layer, 0),
                o[:, 0])

    patches = {"beta_without_2": [(kda, "_heads", beta_without_2)],
               "decay_after_correction": [
                   (kda, "chunk_rule", chunk_rule),
                   (op, "kda_state_update", state_update)],
               }.get(variant, [])

    @contextlib.contextmanager
    def patched():
        was = [(m, n, getattr(m, n)) for m, n, _ in patches]
        for module, name, fn in patches:
            setattr(module, name, fn)
        try:
            yield
        finally:
            for module, name, fn in was:
                setattr(module, name, fn)

    return vcfg, patched


def variant_weights(variant: str, params, donate: bool = False):
    """The weights the variant serves with: as they are, or rounded to
    float8_e4m3's mantissa (in place where ``donate``: a second copy does
    not fit the chip beside the first)."""
    import jax

    if variant != "float8_weights":
        return params
    return jax.jit(lambda p: jax.tree.map(
        lambda w: jax.lax.reduce_precision(w, 8, 3), p),
        donate_argnums=(0,) if donate else ())(params)


def logit_distance(cfg, params, tokens, published, prompt: int,
                   max_len: int, reference_params=None) -> float:
    """The programs' logits against the reference's at EVERY position of
    ``tokens`` (1, T), in units of the reference's deviation: positions
    below ``prompt`` by ``prefill_with_states`` at each length (the chunked
    rule), the others by the decode step fed the row's own next token
    through the cache that prefill left.  The reference reads
    ``reference_params`` (``params``: the same weights)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama, llama_serve

    reference = spec.load_module("references", "solar_open2_decoder")
    tokens = np.asarray(tokens, np.int32)
    T = tokens.shape[1]
    theirs = np.asarray(reference.logits(
        params if reference_params is None else reference_params, tokens,
        published))[0]
    row = np.zeros((1, prompt), np.int32)
    row[0] = tokens[0, :prompt]

    @jax.jit
    def fill(n):
        last, ks, vs, _rows, states, *_ = llama.prefill_with_states(
            params, jnp.asarray(row), n, cfg)
        return last[0], ks, vs, states

    filled = [fill(jnp.asarray([n], jnp.int32))
              for n in range(1, prompt + 1)]
    mine = [np.asarray(f[0]) for f in filled]
    # the whole prompt's rows and states into slot 1, as ``build_prefill``
    # inserts them
    _last, ks, vs, states = filled[-1]
    slots = jnp.asarray([1], jnp.int32)
    cache = llama_serve.init_cache(cfg, 2, max_len)
    cache = llama_serve.insert_states(
        {**cache, "k": llama_serve._insert_rows(cache["k"], ks, slots),
         "v": llama_serve._insert_rows(cache["v"], vs, slots)},
        states, slots)
    active = jnp.asarray([False, True])
    step = jax.jit(lambda carry: llama_serve.decode_step(
        cfg, params, max_len, active, keep_logits=True)(carry, None))
    carry = llama_serve._carry(cache, jnp.zeros(2, jnp.int32),
                               jnp.asarray([0, prompt], jnp.int32))
    for t in range(prompt, T):
        ck, cv, _tok, lens, *state = carry
        carry, (_nxt, _rows, logits) = step(
            (ck, cv, jnp.asarray([0, tokens[0, t]], jnp.int32), lens,
             *state))
        mine.append(np.asarray(logits[1]))
    return float(np.max(np.abs(np.stack(mine) - theirs)) / np.std(theirs))


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--before", type=int, default=700)
    ap.add_argument("--prompt", type=int, default=3000)
    ap.add_argument("--new-tokens", type=int, default=256)
    ap.add_argument("--bucket", type=int, default=4096)
    ap.add_argument("--max-len", type=int, default=4096)
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
        vcfg, patched = broken(variant, cfg)
        requests = []
        for r in range(args.requests):
            rng = np.random.default_rng([args.seed, 3, r])
            requests.append(tuple(
                rng.integers(0, config["vocab_size"], n).astype(np.int32)
                for n in (args.before, args.prompt)))
        # every reply first, under the variant's weights (which take the
        # place of the sound ones on the device), then the reference
        served = variant_weights(variant, params, donate=True)
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

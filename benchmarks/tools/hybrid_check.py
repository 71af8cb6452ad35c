"""Hold a hybrid configuration's serving programs to its reference at the
PUBLISHED widths, outside any timed window: what section 3 of the
``model-configs`` guide asks of a configuration on the chip.

    python3 benchmarks/tools/hybrid_check.py --config granite-4.0-h-micro \\
        --seed 2147486100 [--state-dtypes bfloat16,float32]

One process.  Weights from ``--seed`` by the program's own initialiser,
in the serving type.  Per storage type of the recurrent state:

1. an ``LLMServer`` (dense plane, 5 slots x 1,536 positions, buckets 256,
   512, 1,024) takes prompts of 1, 255, 256, 257 and 1,000 tokens at once
   -- 1, 255 and 256 share one right-padded group, 1,000 is four chunks
   of scan -- and decodes 256 tokens each; then one more short request,
   which lands in a slot a long request held (``--prompts`` ... shrink
   all of it for a rehearsal on the CPU);
2. every reply is read back by the reference's ONE full forward pass over
   prompt + emitted tokens (``teacher_forced_gap``: logits, not tokens,
   in units of the logits' deviation);
3. for the first type only, the same five through the bare programs with
   two slots' recurrent states SWAPPED after the prefill: how far a
   wrong state moves the comparison at these widths and this
   initialisation.

Prints one JSON line per step; the last holds every gap.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks.lib import program, runtime, spec  # noqa: E402


def _ints(text):
    return tuple(int(x) for x in text.split(","))


def _say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--state-dtypes", default="bfloat16,float32")
    # the sizes of the check; a CPU rehearsal shrinks them
    ap.add_argument("--prompts", type=_ints, default=(1, 255, 256, 257,
                                                      1000))
    ap.add_argument("--new-tokens", type=int, default=256)
    ap.add_argument("--max-len", type=int, default=1536)
    ap.add_argument("--buckets", type=_ints, default=(256, 512, 1024))
    ap.add_argument("--bench-dir", default=BENCH_DIR)
    args = ap.parse_args(argv)
    PROMPTS, NEW_TOKENS, MAX_LEN = args.prompts, args.new_tokens, \
        args.max_len
    SLOTS, BUCKETS = len(PROMPTS), args.buckets

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama, llama_serve
    from ray_tpu.serve.llm import LLMServer

    runtime.place_caches()
    with open(os.path.join(args.bench_dir, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    reference = spec.load_module("references", config["reference"],
                                 args.bench_dir)
    rng = np.random.default_rng([args.seed, 3])
    prompts = [rng.integers(0, config["vocab_size"], n).tolist()
               for n in PROMPTS]
    late = rng.integers(0, config["vocab_size"],
                        min(40, BUCKETS[0])).tolist()
    cfg = program.llama_config(config)
    params = jax.jit(lambda key: llama.init_params(key, cfg, cfg.dtype))(
        jax.random.key(args.seed))
    jax.block_until_ready(params)
    _say(event="start", config=args.config, seed=args.seed,
         device=jax.devices()[0].device_kind,
         parameters=sum(x.size for x in jax.tree.leaves(params)))

    def gaps(prompt, tokens):
        gap = reference.teacher_forced_gap(
            params, prompt, tokens, config,
            pad_to=max(PROMPTS) + NEW_TOKENS + 24)
        return float(np.max(gap)), float(np.mean(gap > 0))

    sigma = reference.logit_deviation(
        params, jnp.asarray([prompts[2]]), config)
    _say(event="reference", logit_deviation=sigma)

    out = {"logit_deviation": sigma}
    for n, state_dtype in enumerate(args.state_dtypes.split(",")):
        fields = dict(config, name=f"{args.config}-{state_dtype}")
        fields["program_fields"] = dict(config["program_fields"],
                                        ssm_state_dtype=state_dtype)
        preset = program.install_preset(fields)
        t0 = time.perf_counter()
        server = LLMServer(model_preset=preset, params=params,
                           max_slots=SLOTS, max_len=MAX_LEN,
                           prefill_buckets=BUCKETS, seed=args.seed)
        _say(event="engine", state_dtype=state_dtype,
             start_s=time.perf_counter() - t0,
             pools=server.kv_stats()["state_pool"])

        async def wave(requests):
            return await asyncio.gather(*[server.generate(r)
                                          for r in requests])

        replies = asyncio.run(wave([
            {"prompt": p, "max_new_tokens": NEW_TOKENS} for p in prompts]))
        reused = asyncio.run(wave([
            {"prompt": late, "max_new_tokens": NEW_TOKENS // 4}]))[0]
        server.shutdown()
        rows = {}
        for p, reply in zip(prompts, replies):
            assert len(reply["tokens"]) == NEW_TOKENS
            rows[str(len(p))] = gaps(p, reply["tokens"])
        rows["reused_slot"] = gaps(late, reused["tokens"])
        out[state_dtype] = rows
        _say(event="gaps", state_dtype=state_dtype,
             largest=max(g for g, _ in rows.values()),
             gap_and_share_not_argmax=rows)
        if n:
            continue
        # the bare programs, two slots' states swapped after the prefill
        scfg = server.cfg
        prefill = llama_serve.build_prefill(scfg)
        decode_k = llama_serve.build_decode_k(scfg)
        cache = llama_serve.init_cache(scfg, SLOTS, MAX_LEN)
        toks = np.zeros((SLOTS, BUCKETS[-1]), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        cache, first, _ = prefill(
            params, cache, jnp.asarray(toks),
            jnp.asarray(PROMPTS, jnp.int32),
            jnp.arange(SLOTS, dtype=jnp.int32))
        ssm = cache["ssm"]
        cache = {**cache, "ssm": ssm.at[:, 1].set(ssm[:, 3]).at[:, 3].set(
            ssm[:, 1])}
        tok, lens = first, jnp.asarray(PROMPTS, jnp.int32)
        zeros, no = jnp.zeros(SLOTS, jnp.int32), jnp.zeros(SLOTS, bool)
        emitted = [np.asarray(first)[None]]
        for _ in range(max(1, NEW_TOKENS // 64)):
            cache, step_toks, tok, lens, _ = decode_k(
                params, cache, tok, lens, zeros, zeros, no,
                jnp.ones(SLOTS, bool), k=16, s_active=MAX_LEN)
            emitted.append(np.asarray(step_toks))
        emitted = np.concatenate(emitted)          # (1 + steps, SLOTS)
        out["swapped_states"] = {
            str(len(p)): gaps(p, emitted[:, i].tolist())
            for i, p in enumerate(prompts)}
        _say(event="swapped", state_dtype=state_dtype,
             gap_and_share_not_argmax=out["swapped_states"])
    _say(event="done", **out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

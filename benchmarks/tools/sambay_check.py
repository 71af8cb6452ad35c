"""Hold a decoder-hybrid-decoder's serving programs to its reference at the
PUBLISHED widths, outside any timed window, intact and under faults that
the comparison has to catch.

    python3 benchmarks/tools/sambay_check.py --config \\
        phi-4-mini-flash-reasoning --seed 2147486200 [--variants intact,...]
    python3 benchmarks/tools/sambay_check.py --config \
        phi-4-mini-flash-reasoning --seed 2147486300 --variants joint_bf16 \
        --cell phi-4-mini-flash-reasoning.serve-long-prompt

One process.  Weights from ``--seed`` by the program's own initialiser, in
the serving type.  Per variant an ``LLMServer`` (dense plane, a slot a
prompt, 16,384 positions, buckets 512, 1,024, 4,096, 8,192, 12,288) takes
prompts of 1, 511, 512, 513, 4,095, 4,097 and 12,288 tokens at once --
around the window, the scan's chunks and a bucket's edge; 1, 511 and 512
share one right-padded group -- and decodes 256 tokens each; then one more
short request, which lands in a slot a long request held.  Every reply is
read back by the reference's ONE full forward pass over prompt + emitted
tokens (``teacher_forced_gap``: logits, not tokens, in units of the
logits' deviation; the cell's limit is 0.25).  ``--prompts`` ... shrink
all of it for a rehearsal on the CPU.

Variants: ``intact``; the ``dtype`` block's choices undone -- ``state_bf16``
(the recurrent state STORED in bfloat16), ``stream_bf16`` (the residual
stream carried in bfloat16); two faults patched into
``llama.diff_combine`` here and nowhere in the program -- ``diff_bf16``
(the differential subtraction and lambda in bfloat16) and
``lambda_wrong_half`` (lambda applied to the pair's first softmax);
``joint_bf16``, the three bfloat16 choices at once; and
``float8_weights``, the engine's weights rounded to float8_e4m3's three
mantissa bits, the precision below the configuration's bfloat16 (the
reference keeps the weights as drawn; run it LAST: the rounding is in
place, a second copy of 7.7 GB does not fit beside the engine).

With ``--cell`` the ONE variant named goes through the harness itself:
``run.measure`` of that cell -- its engine, its traffic at its load, its
own ``correct`` -- with the variant patched into this process, and the
line printed is the run's ``correct`` beside the gaps it compared.  There
``float8_weights`` rounds the REFERENCE's weights as it reads them, a
layer at a time (the engine's pools leave no room for a second copy of the
weights, and the comparison is between the two precisions either way).

With a tied head and random weights a model can repeat its input whatever
its layers compute (PERF.md section 6, PR 30): ``repeats`` is the share of
emitted tokens equal to the token before them.

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

VARIANTS = ("intact", "state_bf16", "stream_bf16", "diff_bf16",
            "joint_bf16", "lambda_wrong_half", "float8_weights")
PROGRAM_FIELDS = {"state_bf16": {"ssm_state_dtype": "bfloat16"},
                  "stream_bf16": {"stream_dtype": "bfloat16"},
                  "joint_bf16": {"ssm_state_dtype": "bfloat16",
                                 "stream_dtype": "bfloat16"}}
COMBINE_FAULT = {"diff_bf16": "diff_bf16", "joint_bf16": "diff_bf16",
                 "lambda_wrong_half": "lambda_wrong_half"}


def _ints(text):
    return tuple(int(x) for x in text.split(","))


def _say(**fields) -> None:
    print(json.dumps(fields), flush=True)


def faulty_combine(fault: str):
    """``llama.diff_combine`` with one fault: the subtraction in the
    attention's own type, or lambda on the wrong half."""
    import jax.numpy as jnp

    from ray_tpu.models import llama

    def combine(attn, layer, depth, config):
        f32 = jnp.float32
        B, S, H, W = attn.shape
        lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, f32))
        dots = [jnp.sum(layer[a].astype(f32) * layer[b].astype(f32))
                for a, b in (llama.DIFF_LEAVES[:2], llama.DIFF_LEAVES[2:4])]
        lam = jnp.exp(dots[0]) - jnp.exp(dots[1]) + lam_init
        pairs = attn.reshape(B, S, H // 2, 2, W)
        if fault == "diff_bf16":
            # subtracted in the compute type, lambda rounded to it too
            pairs = pairs.astype(config.dtype)
            a = pairs[..., 0, :] - lam.astype(config.dtype) * pairs[..., 1, :]
        else:
            a = lam * pairs[..., 0, :].astype(f32) \
                - pairs[..., 1, :].astype(f32)
        a = llama.rms_norm(a.astype(f32), layer["sub_norm"],
                           config.norm_eps) * (1.0 - lam_init)
        return a.astype(config.dtype)

    return combine


class _Rounded:
    """A weight read through float8_e4m3's mantissa: what indexing it
    returns is rounded, a layer or a slice at a time."""

    def __init__(self, leaf):
        self.leaf, self.shape = leaf, leaf.shape

    def __getitem__(self, at):
        import jax

        return jax.lax.reduce_precision(self.leaf[at], 8, 3)


def _round_as_read(params):
    """``params`` with the embedding table and every layer's leaves behind
    ``_Rounded`` (the two final norm vectors, handed whole to a jitted
    function, stay as drawn)."""
    import jax

    return {key: jax.tree.map(_Rounded, leaf)
            if key == "embed_tokens" or key.startswith("layers")
            else leaf for key, leaf in params.items()}


def through_the_cell(cell: str, variant: str, seed: int,
                     seconds: float, **measure) -> int:
    """One run of ``cell`` by the harness's own ``run.measure`` (``measure``:
    its further arguments, a test's) with ``variant`` patched into this
    process for the length of the run: the program fields of every config
    the run builds, ``llama.diff_combine``, or the weights the reference
    reads."""
    from benchmarks import run
    from ray_tpu.models import llama

    fields, overrides = program.llama_fields, PROGRAM_FIELDS.get(variant, {})
    combine, load = llama.diff_combine, spec.load_module

    def load_rounding(kind, name, bench_dir=spec.BENCH_DIR):
        module = load(kind, name, bench_dir)
        if kind == "references" and module is not None:
            gap = module.teacher_forced_gap
            module.teacher_forced_gap = lambda params, *args, **kw: gap(
                _round_as_read(params), *args, **kw)
        return module

    program.llama_fields = lambda config: {**fields(config), **overrides}
    if variant in COMBINE_FAULT:
        llama.diff_combine = faulty_combine(COMBINE_FAULT[variant])
    if variant == "float8_weights":
        spec.load_module = load_rounding
    try:
        result, obs = run.measure(
            ["--workload", cell, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", "0"], **measure)
    finally:
        program.llama_fields, llama.diff_combine = fields, combine
        spec.load_module = load
    _say(event="cell_control", cell=cell, variant=variant, seed=seed,
         correct=result["correct"], checks=obs["checks"],
         logit_gaps=obs.get("logit_gaps"), attempted=result["attempted"],
         failed=result["failed"], metrics=result["metrics"])
    return 0


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--variants", default="intact,state_bf16,diff_bf16,"
                    "lambda_wrong_half")
    # the sizes of the check; a CPU rehearsal shrinks them
    ap.add_argument("--prompts", type=_ints,
                    default=(1, 511, 512, 513, 4095, 4097, 12288))
    ap.add_argument("--new-tokens", type=int, default=256)
    ap.add_argument("--max-len", type=int, default=16384)
    ap.add_argument("--buckets", type=_ints,
                    default=(512, 1024, 4096, 8192, 12288))
    ap.add_argument("--bench-dir", default=BENCH_DIR)
    # one variant through the harness's own run of this cell
    ap.add_argument("--cell")
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)
    variants = args.variants.split(",")
    if set(variants) - set(VARIANTS):
        raise SystemExit(f"variants: choose from {VARIANTS}")
    if args.cell:
        if len(variants) != 1:
            raise SystemExit("--cell: one variant a process")
        return through_the_cell(args.cell, variants[0], args.seed,
                                args.seconds)

    import jax
    import numpy as np

    from ray_tpu.models import llama
    from ray_tpu.serve.llm import LLMServer

    runtime.place_caches()
    with open(os.path.join(args.bench_dir, "configs",
                           args.config + ".json")) as f:
        config = json.load(f)
    reference = spec.load_module("references", config["reference"],
                                 args.bench_dir)
    rng = np.random.default_rng([args.seed, 3])
    prompts = [rng.integers(1, config["vocab_size"], n).tolist()
               for n in args.prompts]
    late = rng.integers(1, config["vocab_size"],
                        min(40, args.buckets[0])).tolist()
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
            pad_to=max(args.prompts) + args.new_tokens + 24)
        return float(np.max(gap)), float(np.mean(gap > 0))

    intact_combine = llama.diff_combine
    out = {}
    for variant in variants:
        fields = dict(config, name=f"{args.config}-{variant}")
        fields["program_fields"] = dict(config["program_fields"],
                                        **PROGRAM_FIELDS.get(variant, {}))
        preset = program.install_preset(fields)
        llama.diff_combine = faulty_combine(COMBINE_FAULT[variant]) \
            if variant in COMBINE_FAULT else intact_combine
        served = params
        if variant == "float8_weights":
            # reduce_precision: a convert there and back is folded away
            kept = jax.device_get(params)
            served = jax.jit(lambda p: jax.tree.map(
                lambda w: jax.lax.reduce_precision(w, 8, 3), p),
                donate_argnums=(0,))(params)
        try:
            t0 = time.perf_counter()
            server = LLMServer(model_preset=preset, params=served,
                               max_slots=len(prompts), max_len=args.max_len,
                               prefill_buckets=args.buckets, seed=args.seed)
            _say(event="engine", variant=variant,
                 start_s=time.perf_counter() - t0,
                 pools=server.kv_stats().get("kv_pools"))

            async def wave(requests):
                return await asyncio.gather(*[server.generate(r)
                                              for r in requests])

            t0 = time.perf_counter()
            replies = asyncio.run(wave([
                {"prompt": p, "max_new_tokens": args.new_tokens}
                for p in prompts]))
            reused = asyncio.run(wave([
                {"prompt": late,
                 "max_new_tokens": max(1, args.new_tokens // 4)}]))[0]
            served_s = time.perf_counter() - t0
            server.shutdown()
        finally:
            llama.diff_combine = intact_combine
        if variant == "float8_weights":
            del served, server
            params = jax.device_put(kept)
        rows, repeats = {}, []
        t0 = time.perf_counter()
        for p, reply in zip(prompts, replies):
            assert len(reply["tokens"]) == args.new_tokens
            rows[str(len(p))] = gaps(p, reply["tokens"])
            seq = np.asarray(p[-1:] + reply["tokens"])
            repeats.append(float(np.mean(seq[1:] == seq[:-1])))
        rows["reused_slot"] = gaps(late, reused["tokens"])
        out[variant] = rows
        _say(event="gaps", variant=variant, served_s=served_s,
             reference_s=time.perf_counter() - t0,
             largest=max(g for g, _ in rows.values()),
             repeats=max(repeats), gap_and_share_not_argmax=rows)
    _say(event="done", **out)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

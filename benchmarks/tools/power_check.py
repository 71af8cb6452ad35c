"""Hold a power-retention configuration's serving programs to its reference
at the PUBLISHED widths, outside any timed window, and say what the
comparison that decides ``correct`` makes of broken programs:

    python3 benchmarks/tools/power_check.py --config brumby-14b-base \\
        --seed 2147486500

One process, weights from ``--seed`` by the program's own initialiser, ONE
set of them for every variant and for the reference.  Per variant a real
``LLMServer`` at the cell's engine's geometry (``--slots`` x ``--max-len``,
one prefill bucket, the dense plane; built while the program is patched, so
its own compiled programs are the broken ones) serves ``--requests``
prompts at once, two more than it has slots, so that the last is served in
a slot ANOTHER request held before it; then the comparison the cell's
``correct`` makes of that last reply (``references/brumby_decoder.
teacher_forced_gap``: the reply's logits against the attention form's full
forward pass, and every layer's states, the request taken once more through
the idle engine's own programs into its own cache with every slot
advancing, against the sum as it is written) under the harness's margin.
Per variant one JSON line: the raw gaps' counts, the states' deviation, and
whether it would pass (``kinds/serve_llm.py`` LOGIT_MARGIN).

VARIANTS (``broken``): the program ``intact``; the state KEPT in bfloat16;
the update RUN in bfloat16 (the state rounded to bfloat16 after every
step's update, stored float32); the gate left out (gamma = 0); the
normaliser left out; degree 1 (``phi(u) = u``: a plain linear attention; at
8 slots at most: XLA's update of it holds the states twice);
RoPE left out; the q/k head norms left out; one group's state read by
another's heads (the query heads shifted by a group).

``logit_distance`` is the CPU tests' reading of the same variants: the
program's own LOGITS, prefill and decode through the state, against the
reference's at every position (``tests/test_brumby_serve.py``).
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks.lib import program, runtime, spec  # noqa: E402
from benchmarks.tools.lfm2_check import LOGIT_MARGIN  # noqa: E402

VARIANTS = ("intact", "bf16_state", "bf16_update", "no_gate",
            "no_normaliser", "degree_1", "no_rope", "no_qk_norm",
            "another_groups_state")


def broken(variant: str, cfg):
    """``(the variant's config, a function that gives a context manager
    which patches the program for it)``: the same weights under a program
    that is wrong in one place."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import power_retention as mixer
    from ray_tpu.ops import power_state_update as op

    fields = {"bf16_state": {"ssm_state_dtype": jnp.bfloat16},
              "no_rope": {"rope": False}}.get(variant, {})
    vcfg = dataclasses.replace(cfg, **fields)
    heads, group = mixer._heads, cfg.n_heads // cfg.n_kv_heads

    def no_gate(*args):
        q, k, v, gamma = heads(*args)
        return q, k, v, jnp.zeros_like(gamma)

    def another_group(*args):
        q, k, v, gamma = heads(*args)
        return jnp.roll(q, group, axis=-2), k, v, gamma

    def rounded_update(state, layer, active, decay, q, k, v, eps=op.EPS):
        # the arithmetic's result in bfloat16's mantissa, a step
        new, o = op._xla_update(state, layer, active, decay, q, k, v, eps)
        return jax.lax.reduce_precision(new, 8, 7), o

    def first_power(u):
        # phi(u) = u, in the layout's first row of lanes
        full = jnp.zeros(u.shape[:-1] + (op.shifts(u.shape[-1]),
                                         u.shape[-1]), jnp.float32)
        return full.at[..., 0, :].set(u.astype(jnp.float32))

    patches = {
        "bf16_update": [(op, "power_state_update", rounded_update)],
        "no_gate": [(mixer, "_heads", no_gate)],
        "another_groups_state": [(mixer, "_heads", another_group)],
        "no_normaliser": [(op, "normalised", lambda num, den, eps: num),
                          (op, "power_state_update", op._xla_update)],
        "degree_1": [(op, "phi", first_power), (op, "power", lambda s: s),
                     (op, "power_state_update", op._xla_update)],
        "no_qk_norm": [(mixer, "_head_norm", lambda x, w, eps: x)],
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


def logit_distance(cfg, params, tokens, published, prompt: int,
                   max_len: int, reference_params=None) -> float:
    """The programs' logits against the reference's at EVERY position of
    ``tokens`` (1, T), in units of the reference's deviation: positions
    below ``prompt`` by ``prefill_with_states`` at each length (the chunked
    form), the others by the decode step fed the row's own next token
    through the state that prefill left.  The reference reads
    ``reference_params`` (``params``: the same weights)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama, llama_serve

    reference = spec.load_module("references", "brumby_decoder")
    tokens = np.asarray(tokens, np.int32)
    T = tokens.shape[1]
    theirs = np.asarray(reference.logits(
        params if reference_params is None else reference_params, tokens,
        published))[0]
    row = np.zeros((1, prompt), np.int32)
    row[0] = tokens[0, :prompt]

    @jax.jit
    def fill(n):
        last, _ks, _vs, _rows, states, *_ = llama.prefill_with_states(
            params, jnp.asarray(row), n, cfg)
        return last[0], states

    filled = [fill(jnp.asarray([n], jnp.int32))
              for n in range(1, prompt + 1)]
    mine = [np.asarray(f[0]) for f in filled]
    slots = jnp.asarray([1], jnp.int32)
    cache = llama_serve.insert_states(
        llama_serve.init_cache(cfg, 2, max_len), filled[-1][1], slots)
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


def judge(reference, params, prompt, emitted, config, pad_to):
    """What the cell's check makes of a reply: ``teacher_forced_gap`` (the
    gaps as read, an infinite one in front where the states lie too far)
    under the harness's margin."""
    import numpy as np

    gap = reference.teacher_forced_gap(params, prompt, emitted, config,
                                       pad_to=pad_to)
    finite = gap[np.isfinite(gap)]
    return {"logit_gap_max": float(finite.max()),
            "state_within_limit": bool(np.isfinite(gap).all()),
            "passes": bool(np.max(gap) <= LOGIT_MARGIN)}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--prompt", type=int, default=9000)
    ap.add_argument("--new-tokens", type=int, default=256)
    ap.add_argument("--bucket", type=int, default=9216)
    ap.add_argument("--max-len", type=int, default=18432)
    ap.add_argument("--slots", type=int, default=16,
                    help="the cell's engine's")
    ap.add_argument("--requests", type=int, default=0,
                    help="0: two more than the slots")
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--bench-dir", default=BENCH_DIR)
    args = ap.parse_args(argv)

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
    assert args.prompt + args.new_tokens <= args.max_len
    cfg = program.llama_config(config, max_seq_len=args.max_len)
    params = jax.jit(lambda key: llama.init_params(key, cfg, cfg.dtype))(
        jax.random.key(args.seed))
    rng = np.random.default_rng([args.seed, 3])
    prompts = [rng.integers(0, config["vocab_size"], args.prompt).tolist()
               for _ in range(args.requests or args.slots + 2)]
    out = {}
    for variant in args.variants.split(","):
        _vcfg, patched = broken(variant, cfg)
        # the engine is built from the configuration's file: a field the
        # variant changes goes there
        vconfig = {**config, "name": f"{args.config}-{variant}",
                   "program_fields": {
            **config["program_fields"],
            **({"ssm_state_dtype": "bfloat16"} if variant == "bf16_state"
               else {"rope": False} if variant == "no_rope" else {})}}
        # (degree 1 is no square the kernel could build: XLA's update, which
        # writes a second copy of the stacked states, 4.4 GB at 16 slots)
        slots = min(args.slots, 8) if variant == "degree_1" else args.slots
        with patched():
            server = LLMServer(
                model_preset=program.install_preset(vconfig), params=params,
                max_slots=slots, max_len=args.max_len,
                prefill_buckets=(args.bucket,), seed=args.seed)
            try:
                async def wave():
                    return await asyncio.gather(*[server.generate(
                        {"prompt": p, "max_new_tokens": args.new_tokens})
                        for p in prompts])

                emitted = asyncio.run(wave())[-1]["tokens"]
                got = judge(reference, params, prompts[-1], emitted,
                            vconfig, args.max_len)
            finally:
                server.shutdown()
                del server
        out[variant] = got
        print(json.dumps({"event": "gaps", "variant": variant, **got}),
              flush=True)
    print(json.dumps({"event": "done", **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Hold a Nemotron-H configuration's serving programs (single-sub-layer
blocks, Mamba-2 with groups, latent two-matrix experts, 2 K/V heads as rows)
to its reference at the PUBLISHED widths, outside any timed window, and say
what the comparison that decides ``correct`` makes of broken programs:

    python3 benchmarks/tools/nemotron_check.py \\
        --config nemotron-3-super-120b-a12b --seed 2147486600

``kda_check.py``'s procedure with this family's variants: one process,
weights from ``--seed`` by the program's own initialiser, ONE set of them for
every variant and for the reference; the bare programs (``build_prefill`` /
``build_decode_k`` at the cell's own slots, ``state_check.slots`` of the
file, of which 2 are used: ``lfm2_check.serve_one``) take one request
through the slot ANOTHER request held before it, leave it out of one chunk
that the other slot decodes alone, and decode ``--new-tokens`` through K/V,
the recurrent states and the conv tails; the reference (the recurrence token
by token) reads the reply back in one full forward pass
(``teacher_forced_report``: logits, not tokens), and the variant's programs
take the reply once more, teacher-forced, for the states the slot then holds
(``lib/nemotron_state.py``), as the cell's own check does.  Per variant and
request one JSON line: the raw gaps' counts, each M block's state deviation,
what the cell's check makes of both (``nemotron_h_decoder.judged``) and
whether it would pass (``kinds/serve_llm.py`` LOGIT_MARGIN).

VARIANTS (``broken``): the program ``intact``; the recurrent state stored in
bfloat16 where the file says float32; the recurrence RUN in bfloat16 and its
state stored in float32; the gated norm over the whole 8,192 channels instead
of a group's 1,024; ``relu`` for ``relu^2`` in the routed and the shared
experts; the routed scale (5) dropped; group 0's B and C read by every head;
the weights rounded to float8_e4m3's three mantissa bits (the precision below
the configuration's bfloat16).

``logit_distance`` is the CPU tests' reading of the same variants: the
program's own LOGITS, prefill and cached decode, against the reference's at
every position (``tests/test_nemotron_h_serve.py``).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks.lib import nemotron_state, program, runtime, spec  # noqa: E402
from benchmarks.tools.kda_check import variant_weights  # noqa: E402,F401
from benchmarks.tools.lfm2_check import LOGIT_MARGIN, serve_one  # noqa: E402

VARIANTS = ("intact", "bf16_state", "bf16_recurrence", "whole_width_norm",
            "relu_not_squared", "no_routed_scale", "one_groups_b_and_c",
            "float8_weights")


def broken(variant: str, cfg):
    """``(the variant's config, a function that gives a context manager
    which patches the program for it)``: the same weights under a program
    that is wrong in one place."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from ray_tpu.models import mamba2, moe
    from ray_tpu.ops import ssm_state_update as op

    fields = {"bf16_state": {"ssm_state_dtype": jnp.bfloat16},
              "no_routed_scale": {"moe_routed_scale": 1.0}}.get(variant, {})
    vcfg = dataclasses.replace(cfg, **fields)
    gated_out, chunked, update = (mamba2._gated_out, mamba2.ssd_chunked,
                                  op.ssm_state_update)

    def whole_width(y, z, layer, c):
        return gated_out(y, z, layer, dataclasses.replace(c, ssm_groups=1))

    def first_group(a):
        return jnp.broadcast_to(a[..., :1, :], a.shape)

    def chunked_one_group(x, dt, A, B, C, chunk):
        return chunked(x, dt, A, first_group(B), first_group(C), chunk)

    def update_one_group(ssm, layer, active, decay, dtx, b, c):
        return update(ssm, layer, active, decay, dtx, first_group(b),
                      first_group(c))

    def update_in_bf16(ssm, layer, active, decay, dtx, b, c):
        """``op._xla_update`` with the recurrence's products and sum in
        bfloat16; the state it stores and contracts is float32."""
        bf = jnp.bfloat16
        s = jax.lax.dynamic_index_in_dim(ssm, layer, 0, keepdims=False)
        slots, n, hd = s.shape
        groups = b.shape[1]
        new = (decay.astype(bf)[:, None, :] * s.astype(bf)
               + (b.astype(bf).transpose(0, 2, 1)[..., None]
                  * dtx.astype(bf).reshape(slots, 1, groups, -1)
                  ).reshape(s.shape)).astype(ssm.dtype)
        new = jnp.where(active[:, None, None], new, s)
        y = jnp.einsum("bngj,bgn->bgj", new.astype(jnp.float32).reshape(
            slots, n, groups, hd // groups), c).reshape(slots, hd)
        return (jax.lax.dynamic_update_index_in_dim(ssm, new, layer, 0),
                jnp.where(active[:, None], y, 0.0))

    patches = {"whole_width_norm": [(mamba2, "_gated_out", whole_width)],
               "relu_not_squared": [(moe, "relu2", jax.nn.relu)],
               "one_groups_b_and_c": [
                   (mamba2, "ssd_chunked", chunked_one_group),
                   (op, "ssm_state_update", update_one_group)],
               "bf16_recurrence": [(op, "ssm_state_update", update_in_bf16)],
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


def judge(reference, cfg, params, prompt, emitted, config, max_len):
    """What the reference reads of a reply and of the states that the
    programs of ``cfg`` hold of it, and what the cell's check makes of
    both."""
    import numpy as np

    # (traced anew: a variant patches the program under an unchanged ``cfg``)
    nemotron_state.programs.cache_clear()
    report = reference.teacher_forced_report(params, prompt, emitted, config,
                                             pad_to=max_len)
    deviations = reference.served_deviation(
        params, prompt, emitted, config, max_len, report["states"], cfg=cfg)
    out = reference.judged(report["gap"], deviations)
    return {"counts": reference.gap_counts(report["gap"]),
            "state_deviation": deviations,
            "judged_max": float(np.max(out)),
            "passes": bool(np.max(out) <= LOGIT_MARGIN)}


def logit_distance(cfg, params, tokens, published, prompt: int,
                   max_len: int, reference_params=None) -> float:
    """The programs' logits against the reference's at EVERY position of
    ``tokens`` (1, T), in units of the reference's deviation: positions
    below ``prompt`` by ``prefill_with_states`` at each length (the chunked
    scan), the others by the decode step fed the row's own next token
    through the cache that prefill left.  The reference reads
    ``reference_params`` (``params``: the same weights)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import llama, llama_serve

    reference = spec.load_module("references", "nemotron_h_decoder")
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
    ap.add_argument("--before", type=int, default=300)
    ap.add_argument("--prompt", type=int, default=1500)
    ap.add_argument("--new-tokens", type=int, default=512)
    ap.add_argument("--bucket", type=int, default=2048)
    ap.add_argument("--max-len", type=int, default=2048)
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
    slots = nemotron_state.geometry(config)["slots"]
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
                                 args.new_tokens, args.bucket, args.max_len,
                                 slots=slots)
                       for before, prompt in requests]
        if served is not params:
            del served
            params = init(jax.random.key(args.seed))
        for r, ((_before, prompt), emitted) in enumerate(
                zip(requests, replies)):
            # (the states under the sound weights: a variant of the weights
            # is seen by the tokens)
            with patched():
                got = judge(reference, vcfg, params, prompt, emitted,
                            config, args.max_len)
            out[f"{variant}.{r}"] = got
            print(json.dumps({"event": "gaps", "variant": variant,
                              "request": r, **got}), flush=True)
    print(json.dumps({"event": "done", **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

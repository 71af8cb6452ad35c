"""Hold a trained configuration's ``loss_fn`` to its reference at the
PUBLISHED widths, outside any timed window, seed by seed, and say what the
comparison that decides ``correct`` (``kinds/train_lm.py``: loss, gradient
norm, the gradient kind by kind) makes of broken programs:

    python3 benchmarks/tools/train_check.py --config trinity-mini \\
        --traffic train-8k-1chip --seeds 2147486200,2147486201 \\
        --variants intact,no_band_in_dq

One process.  Per seed: weights by the program's own initialiser, the
traffic's first batch, the reference's loss and gradient ONCE (kept on the
host), then per variant the gradient of the program's ``llama.loss_fn`` --
the function the train step differentiates, with the same kernels -- and
one JSON line: ``loss_gap``, ``grad_norm_gap``, ``gaps`` kind by kind (as
the reference's ``gradient_gaps`` returns them: what the cell compares
with 0.1) and ``worst``.  The lines also go to
``chiprun_out/train_check.jsonl``.

VARIANTS: the program ``intact``; ``no_band_in_dq`` (the dq kernel built
without the window: it walks the causal tiles over the band's blocks);
``bias_out_of_selection`` (top-k of the scores alone); ``no_shared_expert``;
``gate_without_sigmoid`` (the output gate's pre-activation multiplies);
``held_shifted`` (the same matrices taken for experts first + 1 ...);
``rope_on_full`` (the full layers rotate too).
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks.lib import loadgen, program, runtime, spec  # noqa: E402

VARIANTS = ("intact", "no_band_in_dq", "bias_out_of_selection",
            "no_shared_expert", "gate_without_sigmoid", "held_shifted",
            "rope_on_full")


@contextlib.contextmanager
def patched(module, name: str, new):
    """``module.name`` as ``new(the old one)`` inside the block."""
    old = getattr(module, name)
    setattr(module, name, new(old))
    try:
        yield
    finally:
        setattr(module, name, old)


def broken_program(variant: str, config):
    """``(fields of the program's config, a function that gives a context
    manager patching the program)`` of a variant."""
    import importlib

    import jax.numpy as jnp

    from ray_tpu.models import llama

    fields = program.llama_fields(config)
    over, patch = {}, contextlib.nullcontext
    if variant == "bias_out_of_selection":
        over = {"moe_router_bias": False}
    elif variant == "no_shared_expert":
        over = {"moe_shared_size": 0}
    elif variant == "held_shifted":
        first, count = fields["moe_held"]
        over = {"moe_held": (first + 1, count)}
    elif variant == "rope_on_full":
        over = {"nope_kinds": ()}
    elif variant in ("no_band_in_dq", "gate_without_sigmoid"):
        flash = importlib.import_module("ray_tpu.ops.flash_attention")
        if variant == "no_band_in_dq":
            patch = functools.partial(
                patched, flash, "_dq_kernel", lambda old: (
                    lambda *refs, window=None, **kw: old(*refs, **kw)))
        else:
            def gate(old):
                def raw(x, attn, layer, c):
                    h = llama.norm(x, layer, "attn_norm", c).astype(c.dtype)
                    g = llama.matmul(h, layer["w_attn_gate"].astype(c.dtype),
                                     jnp.float32)
                    return (attn.astype(jnp.float32)
                            * g.reshape(attn.shape)).astype(attn.dtype)
                return raw
            patch = functools.partial(patched, llama, "gate_attention",
                                      gate)
    elif variant != "intact":
        raise ValueError(f"unknown variant {variant!r}")
    return over, patch


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variants", default="intact")
    ap.add_argument("--bench-dir", default=BENCH_DIR)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from ray_tpu.models import llama

    runtime.place_caches()

    def load(kind, name):
        with open(os.path.join(args.bench_dir, kind, name + ".json")) as f:
            return json.load(f)

    config, traffic = load("configs", args.config), load("traffic",
                                                         args.traffic)
    reference = spec.load_module("references", config["reference"],
                                 args.bench_dir)
    cfg = program.llama_config(config)
    init = jax.jit(lambda key: llama.init_params(key, cfg))
    programs = {}
    for variant in args.variants.split(","):
        over, patch = broken_program(variant, config)
        vcfg = program.llama_config(config, **over)
        programs[variant] = (patch, jax.jit(functools.partial(
            lambda p, b, c: jax.value_and_grad(llama.loss_fn)(p, b, c),
            c=vcfg)))
    out_dir = os.path.join(os.path.dirname(args.bench_dir), "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        tokens = loadgen.token_batches(traffic, seed, config["vocab_size"])[
            :traffic["batch"]]
        params = init(jax.random.key(seed))
        ref_loss, theirs = reference.loss_and_grads(params, tokens, config)
        theirs = jax.device_get(theirs)
        ref_norm = reference.global_norm(theirs)
        for variant, (patch, grad) in programs.items():
            with patch():   # (the first call of a variant traces under it)
                loss, ours = grad(params, {"tokens": jax.numpy.asarray(
                    tokens)})
            gaps = reference.gradient_gaps(ours, theirs)
            norm = reference.global_norm(ours)
            del ours
            line = {
                "variant": variant, "seed": seed, "loss": float(loss),
                "reference_loss": ref_loss,
                "loss_gap": abs(float(loss) - ref_loss),
                "grad_norm_gap": abs(norm / ref_norm - 1.0),
                "worst": max(gaps, key=gaps.get),
                "gaps": {k: round(v, 5) for k, v in gaps.items()},
                "device": jax.devices()[0].device_kind}
            print(json.dumps(line), flush=True)
            with open(os.path.join(out_dir, "train_check.jsonl"), "a") as f:
                f.write(json.dumps(line) + "\n")
        del params, theirs
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""MFU decomposition + sweep harness on the real chip (not part of the
package).

Times the pieces of the train step separately so the gap between
measured MFU and peak is ATTRIBUTABLE (fwd vs bwd vs optimizer vs the
attention kernel), and sweeps the knobs that move it — remat policy,
flash-attention tile sizes, fused-vs-optax optimizer — so the winning
configuration is reproducible from the CLI and can be recorded as the
preset default.  Each phase runs in its own subprocess (HBM buffers +
jit caches would otherwise accumulate and OOM).

Usage:
  python profile_mfu.py                           # preset defaults
  python profile_mfu.py --batch 8 --remat-policy attn \
      --remat-policy attn_ffn --attn-block 512 --attn-block 1024 \
      --optimizer both                            # 2x2x2 sweep
  python profile_mfu.py --phases fwd,grad,step    # subset
  python profile_mfu.py --one '<json>'            # internal (subprocess)

Per config it emits ONE JSON line with the per-phase breakdown:
fwd/bwd/optimizer seconds, achieved TFLOP/s vs the chip roofline for
the flop-bearing phases, tok/s and 6N MFU; after a sweep it emits a
``winner`` line (highest tok/s) — the configuration to record on the
preset.
"""
import argparse
import itertools
import json
import subprocess
import sys
import time

PHASES = ["fwd", "grad", "step", "attn_flash", "attn_dot", "head"]


def _peak_flops():
    """Per-chip bf16 peak and device kind for the roofline denominator
    (observability/device.py's table).

    Probed in a SUBPROCESS: jax.devices() in the sweep parent would
    take the chip (one process per chip) and every per-phase child
    after it would fail to initialise the device — the whole reason
    phases run in subprocesses.  A failed probe is an error."""
    proc = subprocess.run(
        [sys.executable, __file__, "--device-info"],
        capture_output=True, text=True, timeout=600)
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(
            f"--device-info child failed (exit {proc.returncode}):\n"
            f"{proc.stderr[-2000:]}")
    info = json.loads(lines[-1])
    return info["peak"], info["kind"]


def _device_info():
    import jax

    from ray_tpu.observability.device import peak_bf16_flops

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"profile_mfu.py measures the chip; jax found platform "
            f"{dev.platform!r}")
    print(json.dumps({"peak": peak_bf16_flops(dev.device_kind),
                      "kind": dev.device_kind}))


def _assert_parent_off_chip():
    """One process per chip: each phase is a child that needs it, so
    this sweep parent may import jax (``_n_params`` traces shapes) but
    must never have initialised a backend."""
    from ray_tpu.observability.device import initialized_jax

    if initialized_jax() is not None:
        raise RuntimeError(
            "profile_mfu.py's parent initialised a jax backend: it now "
            "holds the chip its phase children need")


def timeit(fn, *args, warmup=2, steps=5):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def _build_cfg(spec: dict):
    from ray_tpu.models import llama

    preset = getattr(llama.LlamaConfig, spec.get("preset", "llama_440m"))
    return preset(**spec.get("cfg", {}))


def run_one(spec: dict):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    phase = spec["phase"]
    batch = spec["batch"]
    seq = spec.get("seq", 2048)
    cfg = _build_cfg(spec)
    fused = spec.get("fused", False)
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    b = {"tokens": tokens}

    if phase in ("fwd", "grad", "step"):
        if phase == "step":
            state = llama.init_train_state(jax.random.key(0), cfg,
                                           fused=fused)
            step = llama.make_train_step(cfg, donate=False,
                                         fused=fused)
            t = timeit(lambda: step(state, b)[1]["loss"])
        else:
            params = llama.init_params(jax.random.key(0), cfg)
            if phase == "fwd":
                f = jax.jit(lambda p: llama.loss_fn(p, b, cfg))
            else:
                f = jax.jit(lambda p: jax.value_and_grad(llama.loss_fn)(
                    p, b, cfg))
            t = timeit(f, params)
    elif phase in ("attn_flash", "attn_dot"):
        B, S = batch, seq
        Hq, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = jax.random.normal(jax.random.key(2), (B, S, Hq, D),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.key(3), (B, S, Hkv, D),
                              jnp.bfloat16)
        v = jax.random.normal(jax.random.key(4), (B, S, Hkv, D),
                              jnp.bfloat16)
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        if phase == "attn_flash":
            import functools

            from ray_tpu.ops.flash_attention import flash_attention_causal
            attn = functools.partial(flash_attention_causal,
                                     block_q=cfg.attn_block_q,
                                     block_k=cfg.attn_block_k)
        else:
            attn = llama.dot_attention

        g = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum(attn(q, k, v, pos)
                                    .astype(jnp.float32)),
            argnums=(0, 1, 2)))
        t = timeit(g, q, k, v) * cfg.n_layers  # scale to full depth
    elif phase == "head":
        params = llama.init_params(jax.random.key(0), cfg)
        x = jax.random.normal(jax.random.key(5),
                              (batch, seq, cfg.hidden_size), jnp.bfloat16)
        emb = params["embed_tokens"]

        def head_loss(x, emb):
            logits = llama.matmul(x, emb.astype(cfg.dtype).T)[:, :-1]
            logits = logits.astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, tokens[:, 1:][..., None], axis=-1).squeeze(-1)
            return jnp.mean(logz - gold)

        g = jax.jit(jax.grad(head_loss, argnums=(0, 1)))
        t = timeit(g, x, emb)
    else:
        raise SystemExit(f"unknown phase {phase}")
    print(json.dumps({"phase": phase, "s": round(t, 4)}))


def _n_params(spec: dict) -> int:
    import jax

    from ray_tpu.models import llama

    cfg = _build_cfg(spec)
    return llama.param_count(jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg)))


def run_config(spec: dict, phases, peak, seed_timings=None) -> dict:
    """All phases for one configuration (each in a subprocess), plus
    the derived breakdown: bwd/opt slices, achieved TFLOP/s and
    roofline fraction per flop-bearing phase, 6N MFU.
    ``seed_timings`` carries phase results already measured for this
    (policy, block) under another optimizer variant — only the step
    phase depends on the optimizer, so the sweep reuses the rest."""
    res = {"batch": spec["batch"], "preset": spec.get("preset"),
           "cfg": spec.get("cfg", {}),
           "optimizer": "fused" if spec.get("fused") else "optax"}
    for p in PHASES:
        if p != "step" and p + "_s" in (seed_timings or {}):
            res[p + "_s"] = seed_timings[p + "_s"]
    phases = [p for p in phases if p + "_s" not in res]
    for phase in phases:
        proc = subprocess.run(
            [sys.executable, __file__, "--one",
             json.dumps({**spec, "phase": phase})],
            capture_output=True, text=True, timeout=1200)
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("{")]
        if proc.returncode == 0 and lines:
            res[phase + "_s"] = json.loads(lines[-1])["s"]
        else:
            err = (proc.stderr or "").strip().splitlines()
            res[phase + "_err"] = err[-1][:120] if err else proc.returncode
        print(json.dumps(res), flush=True)

    n = _n_params(spec)
    _assert_parent_off_chip()
    toks = spec["batch"] * (spec.get("seq", 2048) - 1)
    res["model_params"] = n

    def tfs(flops_per_tok, seconds):
        return round(toks * flops_per_tok / seconds / 1e12, 1)

    # 2N fwd / 4N bwd / 6N whole-step flops per token (dense-LM
    # approximation, same convention as bench.py's mfu field).
    if "fwd_s" in res:
        res["fwd_tflops_per_s"] = tfs(2 * n, res["fwd_s"])
    if "fwd_s" in res and "grad_s" in res:
        res["bwd_s"] = round(res["grad_s"] - res["fwd_s"], 4)
        if res["bwd_s"] > 0:
            res["bwd_tflops_per_s"] = tfs(4 * n, res["bwd_s"])
        res["bwd_ratio"] = round(res["grad_s"] / res["fwd_s"], 2)
    if "step_s" in res:
        res["tok_per_s"] = round(toks / res["step_s"], 1)
        res["step_tflops_per_s"] = tfs(6 * n, res["step_s"])
        if "grad_s" in res:
            res["opt_s"] = round(res["step_s"] - res["grad_s"], 4)
            res["opt_pct_of_step"] = round(
                100.0 * res["opt_s"] / res["step_s"], 1)
    res["peak_tflops_per_s"] = round(peak / 1e12, 1)
    for key in ("fwd", "bwd", "step"):
        if key + "_tflops_per_s" in res:
            res[key + "_roofline_pct"] = round(
                100.0 * res[key + "_tflops_per_s"] * 1e12 / peak, 1)
    if "step_s" in res:
        res["mfu_6n"] = round(toks / res["step_s"] * 6 * n / peak, 4)
    print(json.dumps(res), flush=True)
    return res


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--preset", default="llama_440m")
    ap.add_argument("--cfg", default="{}",
                    help="extra LlamaConfig overrides (JSON)")
    # Mirrors models.llama.REMAT_POLICIES (not imported here: the
    # sweep parent must stay jax-free so phase subprocesses own the
    # TPU); tests/test_models.py asserts the two stay in sync.
    ap.add_argument("--remat-policy", action="append", default=[],
                    choices=("full", "dots", "dots_saveable", "attn",
                             "attn_ffn"),
                    help="sweep value (repeatable)")
    ap.add_argument("--attn-block", action="append", default=[],
                    help="sweep value (repeatable): BQ or BQ,BK flash "
                         "tile sizes")
    ap.add_argument("--optimizer", choices=("optax", "fused", "both"),
                    default="fused",
                    help="optimizer variant for the step phase")
    ap.add_argument("--phases", default=",".join(PHASES))
    # Legacy positional compatibility: profile_mfu.py [batch] [cfg].
    ap.add_argument("legacy", nargs="*", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.legacy:
        args.batch = int(args.legacy[0])
        if len(args.legacy) > 1:
            args.cfg = args.legacy[1]

    from ray_tpu.compile_cache import place_compile_cache

    place_compile_cache()  # the phase children inherit the directory
    base_cfg = json.loads(args.cfg)
    phases = [p for p in args.phases.split(",") if p]
    peak, kind = _peak_flops()
    print(json.dumps({"device_kind": kind,
                      "peak_tflops_per_s": round(peak / 1e12, 1)}),
          flush=True)

    policies = args.remat_policy or [None]
    blocks = args.attn_block or [None]
    opts = {"optax": [False], "fused": [True],
            "both": [False, True]}[args.optimizer]
    results = []
    for policy, block in itertools.product(policies, blocks):
        cfg = dict(base_cfg)
        if policy is not None:
            cfg["remat_policy"] = policy
        if block is not None:
            parts = [int(x) for x in str(block).split(",")]
            cfg["attn_block_q"] = parts[0]
            cfg["attn_block_k"] = parts[-1]
        # Only the step phase depends on the optimizer variant — the
        # first variant measures everything, the rest reuse its
        # optimizer-independent timings and re-run just "step".
        seed = None
        for fused in opts:
            spec = {"batch": args.batch, "seq": args.seq,
                    "preset": args.preset, "cfg": cfg, "fused": fused}
            res = run_config(spec, phases, peak, seed_timings=seed)
            results.append(res)
            seed = res

    done = [r for r in results if "tok_per_s" in r]
    if len(done) > 1:
        win = max(done, key=lambda r: r["tok_per_s"])
        print(json.dumps({
            "winner": {"cfg": win["cfg"], "optimizer": win["optimizer"],
                       "tok_per_s": win["tok_per_s"],
                       "mfu_6n": win.get("mfu_6n")},
            "note": "record this configuration as the preset default",
        }), flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--one":
        run_one(json.loads(sys.argv[2]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--device-info":
        _device_info()
    else:
        main()

"""chip_smoke.py — the quickest proof that the system still starts on the
chip.

Drives the two main paths once, through the entry points a user calls,
at the full width of the models the repo trains and serves (random
weights from a seed):

- train: ``JaxTrainer(...).fit()`` with one worker whose loop builds
  ``LlamaConfig.llama_440m()`` (flash attention, remat_policy="attn"),
  ``init_train_state`` / ``make_train_step`` (fused AdamW), batch 8 x
  seq 2048 from a ``ray_tpu.data`` pipeline via
  ``get_dataset_shard(...).iter_batches(device_put=True)``, warm-up
  steps then >= 5 steps, ``train.report`` of every loss;
- serve: ``serve.run(serve.deployment(LLMServer).bind(...))`` with
  ``llama_125m`` on BOTH KV planes (dense and paged): one single
  request, then a small concurrent batch, through
  ``handle.generate.remote(...).result()``; and the same on the dense
  plane with the toy hybrid ``hybrid_debug`` (Mamba-2 layers beside
  attention: a recurrent state beside the K/V cache);
- with >= 4 devices also the four-chip phase: the same train path under
  ``MeshSpec(fsdp=4)`` and ``MeshSpec(fsdp=2, tensor=2)``, and ring
  attention compiled once over ``seq=4``.

It checks rather than assumes (see ``train_phase`` / ``serve_phase``)
and exits non-zero if the platform is not ``tpu`` (there is no CPU mode
on the command line) or if any phase fails.  The phases are plain
functions taking a preset and shapes, so tier-1 calls them at
``debug()`` size on the CPU.

ONE PROCESS PER CHIP: the parent never imports jax.  It runs one child
per phase, in turn (``--phase NAME``), so each phase owns the chip
alone, frees all of its HBM on exit, and reports its own peak.  The
children share the placed compile cache (ray_tpu/compile_cache.py).

Last line of stdout on success:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``
Seconds printed along the way are host wall clock, for information;
they are not device metrics.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.abspath(__file__))

# Everything must be done, compilation included, inside this budget.
TOTAL_BUDGET_S = 1150.0

# The engine shape every serve number on record was taken at, and its
# pool re-cut into 64-token blocks for the paged plane (same bytes,
# three times the batch width).
SERVE_ENGINE = dict(model_preset="llama_125m", max_slots=112, max_len=256,
                    prefill_buckets=(32,), decode_chunk=16)
PAGED_ENGINE = dict(paged=True, block_size=64, max_slots=336,
                    num_blocks=1 + 112 * (256 // 64))
# A toy hybrid (Mamba-2 layers beside attention, LlamaConfig.hybrid_debug):
# a recurrent state beside the K/V cache through the same dense plane.
HYBRID_ENGINE = dict(model_preset="hybrid_debug", max_slots=16, max_len=128)


class SmokeFailure(Exception):
    """A check did not hold."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------- shared
def device_info() -> Dict[str, Any]:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def versions() -> Dict[str, str]:
    from importlib import metadata

    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = "not installed"
    return out


def memory_report() -> List[Dict[str, Any]]:
    """Per local device: bytes in use and the peak since process start,
    through the device plane's own sampler.  On TPU the PJRT allocator
    also reports ``peak_bytes_reserved`` — XLA's scratch for a running
    program is counted there, not in ``peak_bytes_in_use``."""
    import jax

    from ray_tpu.observability import device as device_plane

    rows = device_plane.sample_devices() or []
    for row, dev in zip(rows, jax.local_devices()):
        stats = dev.memory_stats() or {}
        if "peak_bytes_reserved" in stats:
            row["peak_reserved"] = int(stats["peak_bytes_reserved"])
    return [{k: r[k] for k in ("device", "used", "peak", "peak_reserved",
                               "limit") if k in r} for r in rows]


def _xla_compiles() -> float:
    """The device plane's own count of XLA backend compilations in this
    process (cache hits included: the event wraps the lookup)."""
    from ray_tpu.observability.metrics import metrics_summary

    return float(metrics_summary().get(
        "ray_tpu_xla_compiles_total", {}).get("backend_compile", 0.0))


class _CompileCacheCounter:
    """Hits and misses of jax's persistent compile cache and the host
    seconds spent in compile-or-fetch, from jax.monitoring."""

    def __init__(self):
        from jax import monitoring

        self.hits = self.misses = 0
        self.compile_wall_s = 0.0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, name: str, **_kw) -> None:
        if name.endswith("/compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.misses += 1

    def _on_secs(self, name: str, secs: float, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.compile_wall_s += float(secs)

    def report(self) -> Dict[str, Any]:
        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "compile_wall_s": round(self.compile_wall_s, 1)}


# ----------------------------------------------------------------- train
def _attention_case(batch: int, seq: int, heads: int, head_dim: int):
    """Seeded q, k, v (B, S, H, D) bf16."""
    import jax
    import jax.numpy as jnp

    shape = (batch, seq, heads, head_dim)
    return tuple(jax.random.normal(jax.random.key(i), shape, jnp.bfloat16)
                 for i in (2, 3, 4))


def _out_and_grads(attn, q, k, v):
    """``attn(q, k, v)`` and its gradients w.r.t. all three under a
    fixed linear readout."""
    import jax
    import jax.numpy as jnp

    weight = jnp.cos(jnp.arange(q.shape[-1], dtype=jnp.float32))

    def scalar(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * weight)

    return (jax.jit(attn)(q, k, v),
            *jax.jit(jax.grad(scalar, argnums=(0, 1, 2)))(q, k, v))


def _require_close(got, ref, what: str, rel_tol: float
                   ) -> Dict[str, float]:
    """Max abs difference as a fraction of the reference's max abs
    value, per tensor; all must be within ``rel_tol``."""
    import jax.numpy as jnp

    out = {}
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        rel = float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
        _require(rel <= rel_tol,  # false for NaN too
                 f"{what}: {name} differs by {rel:.4f} of max "
                 f"(tolerance {rel_tol})")
        out[name] = round(rel, 5)
    return out


def kernel_parity(batch: int = 2, seq: int = 512, heads: int = 8,
                  head_dim: int = 128, rel_tol: float = 0.03
                  ) -> Dict[str, float]:
    """Flash attention, forward and gradients, against the einsum
    reference on a small input."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import dot_attention
    from ray_tpu.ops.flash_attention import flash_attention

    q, k, v = _attention_case(batch, seq, heads, head_dim)
    pos = jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32), (batch, seq))
    got = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
    ref = _out_and_grads(
        lambda q, k, v: dot_attention(q, k, v, pos), q, k, v)
    return _require_close(got, ref, "flash vs einsum reference", rel_tol)


def _mosaic_calls(hlo_text: str) -> List[str]:
    """The distinct Mosaic custom calls of a compiled HLO module, each
    as its result shapes — per-shard shapes under a mesh when the
    kernel runs per shard, global ones when every device does all of
    the work."""
    shape = re.compile(r"\b(?:bf16|f32|s32)\[[\d,]*\]")
    seen = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        results = " ".join(shape.findall(line.partition("custom-call(")[0]))
        if results not in seen:
            seen.append(results)
    return seen


def _leaf_specs(tree) -> Dict[str, str]:
    import jax

    return {jax.tree_util.keystr(path):
            str(getattr(leaf.sharding, "spec", leaf.sharding))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _train_loop(config: Dict[str, Any]) -> None:
    """``train_loop_per_worker``: the train main path, with the checks
    that need the live state made along the way.  Everything learned is
    handed back through ``train.report``."""
    import jax

    from ray_tpu import train
    from ray_tpu.models import llama

    cfg = getattr(llama.LlamaConfig, config["preset"])(
        **config["cfg_overrides"])
    t0 = time.perf_counter()
    state = llama.init_train_state(jax.random.key(config["seed"]), cfg,
                                   fused=True)
    jax.block_until_ready(state)
    init_wall_s = time.perf_counter() - t0
    step = llama.make_train_step(cfg, fused=True)
    batches = train.get_dataset_shard("train").iter_batches(
        batch_size=config["batch"], drop_last=True, prefetch_batches=2,
        device_put=True)

    facts: Dict[str, Any] = {
        "init_wall_s": round(init_wall_s, 1),
        "memory_after_init": memory_report(),
        "state_specs_before": _leaf_specs(state),
    }
    batch = next(batches)
    facts["batch_sharding"] = str(batch["tokens"].sharding)
    t0 = time.perf_counter()
    hlo = step.lower(state, batch).compile().as_text()
    facts["compile_wall_s"] = round(time.perf_counter() - t0, 1)
    facts["mosaic_calls"] = _mosaic_calls(hlo)
    facts["hlo_all_gathers"] = len(re.findall(r"\ball-gather(?:-start)?\(",
                                              hlo))
    del hlo

    n_steps = config["warmup"] + config["steps"]
    facts["losses"] = []
    for i in range(n_steps):
        old_leaves = jax.tree.leaves(state)
        old_shardings = [leaf.sharding for leaf in old_leaves]
        state, metrics = step(state, batch)
        loss = float(metrics["loss"])  # waits for the step
        facts["losses"].append(loss)
        report = {"step": i, "loss": loss,
                  "grad_norm": float(metrics["grad_norm"])}
        if i == 0:
            facts["donation_honoured"] = all(
                leaf.is_deleted() for leaf in old_leaves)
            facts["memory_after_step1"] = memory_report()
            facts["state_specs_after"] = _leaf_specs(state)
            facts["leaves_resharded"] = [
                jax.tree_util.keystr(path) for (path, leaf), was in zip(
                    jax.tree_util.tree_leaves_with_path(state),
                    old_shardings)
                if not leaf.sharding.is_equivalent_to(was, leaf.ndim)]
        del old_leaves, old_shardings
        if i == n_steps - 1:
            facts["memory_at_end"] = memory_report()
            report["facts"] = facts
        else:
            batch = next(batches)
        train.report(report)


def train_phase(preset: str = "llama_440m", batch: int = 8,
                seq: int = 2048, warmup: int = 2, steps: int = 5,
                mesh=None, cfg_overrides: Optional[dict] = None,
                seed: int = 0) -> Dict[str, Any]:
    """The train main path through ``JaxTrainer.fit()``; ``mesh`` is a
    ``MeshSpec`` or None (one device).  Checks: the last step's report
    arrived; losses finite and changing from step to step; the old
    state's buffers donated; the state's shardings unchanged by the
    step; and on TPU the compiled step holds the Mosaic custom calls
    (flash forward, dq, dk/dv) — compiled, not interpreted, not the
    einsum."""
    import jax
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.models import llama
    from ray_tpu.train import JaxTrainer, ScalingConfig

    cfg_overrides = dict(cfg_overrides or {})
    cfg = getattr(llama.LlamaConfig, preset)(**cfg_overrides)
    n_steps = warmup + steps
    rows = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (n_steps * batch, seq)).astype(np.int32)
    ds = rd.from_blocks([{"tokens": rows[i:i + batch]}
                         for i in range(0, len(rows), batch)])
    trainer = JaxTrainer(
        _train_loop,
        train_loop_config=dict(preset=preset, cfg_overrides=cfg_overrides,
                               batch=batch, warmup=warmup, steps=steps,
                               seed=seed),
        scaling_config=ScalingConfig(num_workers=1, mesh=mesh),
        datasets={"train": ds})
    try:
        result = trainer.fit()
    finally:
        ray_tpu.shutdown()

    _require(result.metrics.get("step") == n_steps - 1,
             f"last report is {result.metrics.get('step')}, expected "
             f"step {n_steps - 1}")
    facts = result.metrics["facts"]
    losses = facts["losses"]
    print(f"train[{preset} b{batch} s{seq} mesh={mesh}]: losses "
          + " ".join(f"{x:.4f}" for x in losses), flush=True)
    for key in ("init_wall_s", "compile_wall_s", "batch_sharding",
                "donation_honoured", "hlo_all_gathers", "mosaic_calls",
                "memory_after_init", "memory_after_step1",
                "memory_at_end"):
        print(f"  {key}: {facts[key]}", flush=True)
    before, after = facts["state_specs_before"], facts["state_specs_after"]
    moved = {k: (before[k], after[k]) for k in facts["leaves_resharded"]}
    for when, specs in (("before", before), ("after", after)):
        by_spec: Dict[str, int] = {}
        for spec in specs.values():
            by_spec[spec] = by_spec.get(spec, 0) + 1
        print(f"  state leaves by sharding {when} the step: {by_spec}",
              flush=True)
    print(f"  leaves the step resharded: {moved or 'none'}", flush=True)

    _require(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    _require(all(a != b for a, b in zip(losses, losses[1:])),
             f"loss did not change between two steps: {losses}")
    _require(facts["donation_honoured"],
             "the train step did not donate the old state's buffers")
    _require(not moved, f"the step changed state shardings: {moved}")
    if jax.default_backend() == "tpu" and cfg.attention_impl == "flash":
        _require(len(facts["mosaic_calls"]) >= 3,
                 "compiled train step holds fewer than 3 distinct Mosaic "
                 f"custom calls: {facts['mosaic_calls']}")
    return {"losses": losses, "mosaic_calls": facts["mosaic_calls"],
            "batch_sharding": facts["batch_sharding"],
            "memory_at_end": facts["memory_at_end"]}


def ring_phase(seq_devices: int = 4, batch: int = 1, seq: int = 4096,
               heads: int = 8, head_dim: int = 128,
               rel_tol: float = 0.03) -> Dict[str, Any]:
    """Ring attention over ``seq=seq_devices``, forward and gradients,
    against single-device flash attention on the same input."""
    import jax

    from ray_tpu.ops.flash_attention import flash_attention
    from ray_tpu.ops.ring_attention import ring_attention
    from ray_tpu.parallel import MeshSpec, logical_sharding, use_mesh

    q, k, v = _attention_case(batch, seq, heads, head_dim)
    ref = _out_and_grads(
        lambda q, k, v: flash_attention(q, k, v, causal=True), q, k, v)
    mesh = MeshSpec(seq=seq_devices).build(jax.devices()[:seq_devices])
    with use_mesh(mesh):
        sharding = logical_sharding(("batch", "seq", "heads", "head_dim"))
        got = _out_and_grads(
            ring_attention,
            *(jax.device_put(x, sharding) for x in (q, k, v)))
    out = _require_close(got, ref, "ring vs flash", rel_tol)
    print(f"ring[seq={seq_devices} S{seq}]: max diff / max ref {out}",
          flush=True)
    return out


# ----------------------------------------------------------------- serve
def serve_phase(paged: bool, engine: Optional[dict] = None,
                n_concurrent: int = 8, prompt_len: int = 24,
                max_new_tokens: int = 32) -> Dict[str, Any]:
    """The serve main path: ``serve.run`` of an ``LLMServer`` deployment
    (warm-up compiles every program), one request, then ``n_concurrent``
    at once.  Checks: every request returns exactly ``max_new_tokens``
    in-vocabulary tokens and a ``ttft_ms``; the replica is healthy
    afterwards; and the device plane's XLA-compile counter — which must
    have counted the warm-up — does not move between the end of warm-up
    and the last response."""
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models import llama
    from ray_tpu.observability import device as device_plane
    from ray_tpu.serve.llm import LLMServer

    kw = dict(SERVE_ENGINE)
    if paged:
        kw.update(PAGED_ENGINE)
    kw.update(engine or {})
    vocab = getattr(llama.LlamaConfig, kw["model_preset"])().vocab_size
    rng = np.random.default_rng(0)

    def request():
        return {"prompt": rng.integers(1, vocab, prompt_len).tolist(),
                "max_new_tokens": max_new_tokens}

    # One sampler tick now: the compile listener installs here instead
    # of at the sampler thread's first period.
    device_plane.sample_once()
    t0 = time.perf_counter()
    try:
        handle = serve.run(serve.deployment(LLMServer).bind(**kw))
        warmup_wall_s = time.perf_counter() - t0
        compiles_warm = _xla_compiles()
        outs = [handle.generate.remote(request()).result(timeout=600)]
        outs += [r.result(timeout=600) for r in
                 [handle.generate.remote(request())
                  for _ in range(n_concurrent)]]
        healthy = handle.check_health.remote().result(timeout=60)
        compiles_end = _xla_compiles()
        memory = memory_report()
    finally:
        serve.shutdown()
        ray_tpu.shutdown()

    plane = "paged" if paged else "dense"
    print(f"serve[{kw['model_preset']} {plane}]: {len(outs)} requests "
          f"answered; warm-up wall_s {warmup_wall_s:.1f}; XLA compiles "
          f"after warm-up {compiles_warm:.0f}, after the last response "
          f"{compiles_end:.0f}; healthy {healthy}", flush=True)
    print(f"  memory: {memory}", flush=True)
    for out in outs:
        toks = out["tokens"]
        _require(len(toks) == max_new_tokens,
                 f"{len(toks)} tokens returned, asked {max_new_tokens}")
        _require(all(0 <= int(t) < vocab for t in toks),
                 f"token outside the vocabulary: {toks}")
        _require(isinstance(out.get("ttft_ms"), (int, float))
                 and out["ttft_ms"] > 0, f"no ttft_ms: {out.get('ttft_ms')}")
    _require(healthy is True, "check_health() is not true after serving")
    _require(compiles_warm > 0,
             "the XLA-compile counter did not count the warm-up")
    _require(compiles_end == compiles_warm,
             f"{compiles_end - compiles_warm:.0f} XLA compile(s) after "
             f"warm-up: a request paid a compile")
    return {"requests": len(outs), "xla_compiles": compiles_end,
            "memory": memory}


# ------------------------------------------------------- process plumbing
SINGLE_CHIP_PHASES = ("train", "serve_dense", "serve_paged",
                      "serve_hybrid")
FOUR_CHIP_PHASES = ("train_fsdp4", "train_fsdp2_tensor2", "ring4")


def run_phase(name: str) -> Dict[str, Any]:
    from ray_tpu.parallel import MeshSpec

    phases = {
        "train": lambda: {"kernel_parity": kernel_parity(),
                          **train_phase()},
        "serve_dense": lambda: serve_phase(paged=False),
        "serve_paged": lambda: serve_phase(paged=True),
        "serve_hybrid": lambda: serve_phase(paged=False,
                                            engine=HYBRID_ENGINE),
        "train_fsdp4": lambda: train_phase(mesh=MeshSpec(fsdp=4)),
        "train_fsdp2_tensor2": lambda: train_phase(
            mesh=MeshSpec(fsdp=2, tensor=2)),
        "ring4": ring_phase,
    }
    if name not in phases:
        raise SystemExit(f"chip_smoke: unknown phase {name!r}; one of "
                         f"{', '.join(phases)}")
    return phases[name]()


def _child_main(name: str) -> int:
    """One phase in this process, which owns the chip while it lives."""
    import jax

    counter = _CompileCacheCounter()
    device = device_info()
    print(f"chip_smoke[{name}]: platform={device['platform']} "
          f"device_kind={device['kind']!r} count={device['count']} "
          f"versions={versions()} "
          f"compile_cache={jax.config.jax_compilation_cache_dir}",
          flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: platform is {device['platform']!r}, not "
              f"'tpu' — this script runs on the chip only", flush=True)
        return 2
    t0 = time.perf_counter()
    try:
        result = run_phase(name)
    except SmokeFailure as e:
        print(f"chip_smoke[{name}]: CHECK FAILED: {e}", flush=True)
        return 1
    print(json.dumps({"phase": name, "ok": True, "device": device,
                      "wall_s": round(time.perf_counter() - t0, 1),
                      **counter.report(), **result}), flush=True)
    return 0


def _run_child(name: str, deadline: float) -> Dict[str, Any]:
    """Run one phase in a child of its own and return the JSON object
    on its last line.  The child (and anything it started) is killed at
    the deadline and whenever this function is left."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name],
        cwd=ROOT, stdout=subprocess.PIPE, text=True,
        env={**os.environ, "PYTHONUNBUFFERED": "1"},
        start_new_session=True)

    def kill():
        try:
            os.killpg(proc.pid, 9)
        except ProcessLookupError:
            pass

    timer = threading.Timer(max(1.0, deadline - time.monotonic()), kill)
    timer.start()
    last = ""
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            last = line.strip() or last
        rc = proc.wait()
    finally:
        timer.cancel()
        kill()
    if rc != 0:
        raise SmokeFailure(f"phase {name} exited with code {rc}")
    return json.loads(last)


def main(argv: List[str]) -> int:
    from ray_tpu.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    if len(argv) == 2 and argv[0] == "--phase":
        return _child_main(argv[1])
    if argv:
        raise SystemExit("usage: python chip_smoke.py")
    print(f"chip_smoke: compile cache at {cache_dir}", flush=True)
    deadline = time.monotonic() + TOTAL_BUDGET_S
    phases = list(SINGLE_CHIP_PHASES)
    results: Dict[str, Any] = {}
    device = None
    try:
        for name in phases:
            res = _run_child(name, deadline)
            results[name] = {k: res[k] for k in
                             ("wall_s", "compile_wall_s", "cache_hits",
                              "cache_misses")}
            if device is None:
                device = res["device"]
                if device["count"] >= 4:
                    phases.extend(FOUR_CHIP_PHASES)
                else:
                    print(f"chip_smoke: {device['count']} device(s): the "
                          f"four-chip phase needs >= 4 and is not run",
                          flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", flush=True)
        return 1
    # One process per chip: the children owned it, this parent never
    # so much as imported jax.
    if "jax" in sys.modules:
        raise RuntimeError("chip_smoke parent imported jax")
    print(f"chip_smoke: phases {json.dumps(results)}", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

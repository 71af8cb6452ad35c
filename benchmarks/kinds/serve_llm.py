"""Kind ``serve_llm``: an ``LLMServer`` deployment served through the
entry points a user calls — ``serve.run(serve.deployment(LLMServer)
.options(...).bind(...))`` and ``handle.generate.remote(request)
.result()`` — in the one process that owns the chip, with the load
generator beside it.

Cell file: ``engine`` (``LLMServer`` constructor arguments; everything
left out keeps its default), ``deployment`` (``serve.deployment``
options).  Traffic: ``generator: "requests"``.

``correct``: CHECK_REQUESTS of the window's own requests — the longest
and others drawn by the seed, so decoded among the cell's full batch, at
its prefill and attended-length buckets, in rows other requests held
before — are read back after the window: the plain float32 reference is
run teacher-forced over prompt + the engine's tokens, and at every
emitted position the reference's logit of the emitted token must lie
within LOGIT_MARGIN of the reference's top logit (logits, not token
equality: with random weights the argmax flips on rounding).  Also:
every request of the window returned exactly the tokens asked, all
inside the vocabulary; ``check_health()`` is true afterwards; no
compilation inside the window.
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Any, Dict

import numpy as np

from benchmarks.lib import loadgen, program, runtime

# The engine computes in bfloat16, the reference in float32 on the same
# bfloat16 weights.  Logits of the random-weight models have a standard
# deviation near 1; bfloat16 rounding through 24 layers moves one by a
# few hundredths, so a greedy token that is not the reference's argmax is
# a near-tie: the largest gap measured on the v5e is 0.040 (PERF.md
# section 6), and the margin is six times that, since the largest of
# some 500 positions a run grows with the runs.  A wrong cache row,
# position or mask makes the emitted token an arbitrary one: gap ~ 4 (top
# of ~92k draws).  int8/fp8 arithmetic in place of bfloat16 gives gaps of
# several tenths.
LOGIT_MARGIN = 0.25
CHECK_REQUESTS = 4
TRACE_SECONDS = 4.0


class Engine:
    """The deployment, up for the length of a ``with`` block: weights on
    the device from the seed, ``serve.run``, ``send``; torn down after."""

    def __init__(self, ctx: runtime.Context):
        self.ctx = ctx
        self.facts: Dict[str, Any] = {}

    def __enter__(self) -> "Engine":
        import jax

        from ray_tpu import serve
        from ray_tpu.models import llama
        from ray_tpu.serve.llm import LLMServer

        ctx, cell = self.ctx, self.ctx.cell
        self.engine = {k: tuple(v) if isinstance(v, list) else v
                       for k, v in cell.workload["engine"].items()}
        preset = program.install_preset(cell.config)
        cfg = program.llama_config(cell.config)
        # Weights: on the device, from the seed, in the serving type, by
        # ONE jitted call of the program's own initialiser.
        t0 = time.perf_counter()
        self.params = jax.jit(
            lambda key: llama.init_params(key, cfg, cfg.dtype))(
            jax.random.key(ctx.seed))
        jax.block_until_ready(self.params)
        self.facts["weights_s"] = time.perf_counter() - t0
        runtime.program_counters()   # installs the program's listener
        deployment = serve.deployment(LLMServer).options(
            **cell.workload.get("deployment", {}))
        t0 = time.perf_counter()
        try:
            self.handle = serve.run(deployment.bind(
                model_preset=preset, params=self.params, seed=ctx.seed,
                **self.engine))
        except BaseException:
            self.__exit__(None, None, None)
            raise
        self.facts["engine_start_s"] = time.perf_counter() - t0
        return self

    def send(self, request: Dict[str, Any]):
        return self.handle.generate.remote(request)

    def healthy(self) -> bool:
        return self.handle.check_health.remote().result(timeout=60) is True

    def __exit__(self, *exc) -> None:
        import ray_tpu
        from ray_tpu import serve

        serve.shutdown()
        ray_tpu.shutdown()


def check_against_reference(engine: Engine, generator, measured):
    """Per checked request, the largest gap between the reference's top
    logit and its logit of a token the engine emitted.  Checked: the
    longest complete request of the window and CHECK_REQUESTS - 1 others
    drawn by the seed, every row padded to the engine's ``max_len`` (one
    compiled shape of the reference)."""
    cell, ctx = engine.ctx.cell, engine.ctx
    done = sorted((r for r in measured if r.index in generator.exchanges),
                  key=lambda r: r.prompt_tokens + r.got_tokens)
    if not done:
        return [float("inf")]
    others = np.random.default_rng([ctx.seed, 9]).permutation(len(done) - 1)
    picked = [done[-1]] + [done[i] for i in others[:CHECK_REQUESTS - 1]]
    gaps = []
    for r in picked:
        prompt, tokens = generator.exchanges[r.index]
        gaps.append(float(np.max(cell.reference.teacher_forced_gap(
            engine.params, prompt, tokens, cell.config,
            pad_to=engine.engine["max_len"]))))
    return gaps


def run(ctx: runtime.Context) -> Dict[str, Any]:
    cell = ctx.cell
    watch = runtime.compile_watch()
    tracer = runtime.Tracer(ctx.trace, ctx.out_dir)
    marks: Dict[str, Any] = {}

    def on_open() -> float:
        marks["compiles_open"] = watch.count
        marks["program_open"] = runtime.program_counters()
        marks["t_open"] = time.perf_counter()
        if tracer.enabled:   # the last TRACE_SECONDS of the window
            timer = threading.Timer(
                max(0.0, ctx.seconds - TRACE_SECONDS), tracer.start)
            timer.daemon = True
            timer.start()
            marks["timer"] = timer
        return marks["t_open"]

    def on_close() -> float:
        marks["t_close"] = time.perf_counter()
        if tracer.enabled:
            marks["timer"].join()
            tracer.stop()
        marks["window_compiles"] = watch.count - marks["compiles_open"]
        return marks["t_close"]

    obs: Dict[str, Any] = {"kind": "serve_llm"}
    generator = None
    engine = Engine(ctx)
    try:
        with engine:
            generator = loadgen.LoadGenerator(
                cell.traffic, ctx.seed, cell.config["vocab_size"],
                engine.send)
            log = generator.run(ctx.seconds, on_open, on_close)
            healthy = engine.healthy()
            program_close = runtime.program_counters()
            obs["memory"] = runtime.memory_peaks(ctx.devices)
            measured = log.measured()
            t0 = time.perf_counter()
            obs["logit_gaps"] = check_against_reference(
                engine, generator, measured)
            obs["logit_gap_max"] = max(obs["logit_gaps"])
            obs["reference_s"] = time.perf_counter() - t0
    finally:
        if generator is not None:
            obs["callers_left"] = generator.join(30.0)
    obs.update(engine.facts)

    with open(os.path.join(ctx.out_dir, "requests.jsonl"), "w") as f:
        f.write(json.dumps({"t_open": log.t_open, "t_close": log.t_close,
                            "closed_loop": log.closed_loop}) + "\n")
        for r in log.records:
            f.write(json.dumps(dataclasses.asdict(r)) + "\n")
    complete = [r for r in measured
                if r.ok and r.got_tokens == r.asked_tokens
                and r.tokens_valid]
    checks = {
        "engine tokens within the reference's logit margin":
            obs["logit_gap_max"] <= LOGIT_MARGIN,
        "every request returned the tokens asked, in vocabulary":
            len(complete) == len(measured) and len(measured) > 0,
        "healthy after the window": healthy,
        "no compilation in the window": marks["window_compiles"] == 0,
    }
    obs.update({
        "checks": checks,
        # each number ``correct`` compares, beside its limit
        "compared": {
            "logit_gap_max": [obs["logit_gap_max"], LOGIT_MARGIN],
            "requests_incomplete": [len(measured) - len(complete), 0],
            "window_compiles": [marks["window_compiles"], 0]},
        "attempted": len(measured),
        "failed": len(measured) - len(complete),
        "setup_s": marks["t_open"] - ctx.t_process,
        "log": log, "measured": measured,
        "t_open": log.t_open, "t_close": log.t_close,
        "window_compiles": marks["window_compiles"],
        "program_window_compiles": program_close["xla_compiles"]
        - marks["program_open"]["xla_compiles"],
        "trace": runtime.read_trace(ctx),
        "trace_span": [tracer.t_start, tracer.t_stop],
        "decode_chunk": engine.engine.get("decode_chunk", 16),
    })
    return obs

"""Headline benchmark: training-step throughput on the flagship model.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
The reference publishes no absolute model-level throughput (BASELINE.md:
"published" is empty), so vs_baseline is null until a measured reference
number exists.

Runs on the TPU only: without the chip it refuses to start, and a phase
that fails makes the exit status non-zero (its ``*_error`` key says
which).  This process holds the chip; the ``Cluster`` worker nodes the
host phases start are held to the CPU (``start_worker_process``).
"""

from __future__ import annotations

import json
import sys
import time


def _peak_bf16_flops(device_kind: str):
    """Per-chip bf16 peak by device kind — ONE table, owned by the
    device plane (observability/device.py) so the live MFU gauges and
    these offline bench/profile_mfu numbers can never disagree about
    the same hardware."""
    from ray_tpu.observability.device import peak_bf16_flops

    return peak_bf16_flops(device_kind)


# The paged baseline's pool shape, written ONCE: the dense cache's
# 112 x 256 reservation re-cut into 64-token blocks (448 usable + the
# null block), batch width 3x.  The quantized phases derive their
# byte budgets from these numbers, so the spec_int8 / kv_quant ratios
# stay an equal-bytes comparison if the baseline is ever retuned.
_PAGED_BASE = dict(block_size=64, max_slots=336,
                   num_blocks=1 + 112 * (256 // 64))


def _paged_base_pool_bytes(cfg) -> int:
    """bf16 K+V bytes of the paged baseline's usable blocks."""
    return (2 * (_PAGED_BASE["num_blocks"] - 1) * cfg.n_layers
            * _PAGED_BASE["block_size"] * cfg.n_kv_heads
            * cfg.head_dim * 2)


def _serve_bench(n_requests: int = 256, paged: bool = False,
                 engine_kw: dict = None, suffix: str = None,
                 vocab: int = 32000) -> dict:
    """Continuous-batched 125M decode: concurrent requests through the
    serve handle; returns req/s, p50 TTFT, decode tok/s.  All compile
    paths warm up at deployment init, so the timed run measures steady
    state.

    ``paged=True`` runs the SAME workload through the paged-KV plane
    at the SAME pool memory: the dense cache reserves
    112 slots × 256 positions up front, so the paged pool gets exactly
    that many 64-token blocks — but because live requests only touch
    ~1-2 blocks each (56 live positions), the same bytes carry 3x the
    batch width (max_slots=336).  That memory→batch→throughput
    conversion is the vLLM >2x claim under test; keys get a ``_paged``
    suffix so BENCH rounds compare the planes directly.

    ``engine_kw`` overrides the engine shape (the spec-decode and
    kv-quant phases — and the CPU-shaped tier-1 smokes — reuse this
    harness); engines with ``spec_k`` also report their accept rate
    from the replica's own counters."""
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer

    # max_slots 112 measured best on v5e (r5): ~112 req/s / ~335 ms
    # saturated p50 TTFT vs 88.4 / 573 at 64 slots (admission waves
    # dominate the saturated tail; 128 slots regresses throughput).
    kw = dict(model_preset="llama_125m", max_slots=112, max_len=256,
              prefill_buckets=(32,), decode_chunk=16)
    if paged:
        kw.update(paged=True, **_PAGED_BASE)
    kw.update(engine_kw or {})
    prompt_len = min(24, max(kw["prefill_buckets"]))
    handle = serve.run(serve.deployment(LLMServer).bind(**kw))
    try:
        rng = np.random.default_rng(0)

        def req():
            return {"prompt":
                    rng.integers(1, vocab, prompt_len).tolist(),
                    "max_new_tokens": 32}

        handle.generate.remote(req()).result(timeout=600)  # end-to-end warm
        # Phase 1 — TTFT at light load (staggered singles): first-token
        # latency unconfounded by queue depth, the standard way serving
        # TTFT is quoted.
        ttfts = []
        for _ in range(12):
            out = handle.generate.remote(req()).result(timeout=600)
            ttfts.append(out["ttft_ms"])
        ttfts.sort()
        # Phase 2 — saturation throughput.
        t0 = time.perf_counter()
        outs = [r.result(timeout=600) for r in
                [handle.generate.remote(req())
                 for _ in range(n_requests)]]
        dt = time.perf_counter() - t0
        spec = None
        if kw.get("spec_k"):
            spec = handle.kv_stats.remote().result(
                timeout=60).get("spec")
    finally:
        serve.shutdown()
    sat_ttfts = sorted(o["ttft_ms"] for o in outs)
    sfx = suffix if suffix is not None else ("_paged" if paged else "")
    out = {
        f"serve_req_per_s{sfx}": round(n_requests / dt, 2),
        f"serve_p50_ttft_ms{sfx}": round(ttfts[len(ttfts) // 2], 1),
        f"serve_p50_ttft_saturated_ms{sfx}": round(
            sat_ttfts[len(sat_ttfts) // 2], 1),
        f"serve_decode_tok_per_s{sfx}": round(
            sum(len(o["tokens"]) for o in outs) / dt, 1),
    }
    if spec:
        # Canonical unsuffixed names belong to the plain spec phase;
        # other spec-carrying engines (e.g. "_spec_int8") keep their
        # suffix so one phase can't clobber another's accept rate.
        ssfx = "" if sfx == "_spec" else sfx
        out[f"spec_decode_accept_rate{ssfx}"] = spec["accept_rate"]
        out[f"spec_decode_k{ssfx}"] = spec["k"]
    return out


# Spec-decode engine shape for the bench model: a 3-of-12-layer
# self-draft (zero extra weights) proposing 4 tokens per verify pass.
_SPEC_ENGINE = dict(spec_k=4, draft_layers=3)


def _kv_quant_bench(n_requests: int = 192, engine_kw: dict = None,
                    base_blocks: int = None, vocab: int = 32000) -> dict:
    """Quantized-KV capacity conversion at the SAME pool bytes: the
    bf16 paged pool's byte budget re-cut into int8 blocks carries ~2x
    the blocks, and the engine converts them into decode batch width
    (``max_slots`` scaled with the block count).  Reports the block
    counts (the capacity math, verifiable from the JSON alone) and
    the throughput ratio."""
    from ray_tpu.models import llama
    from ray_tpu.serve.kv_cache import blocks_for_bytes

    kw = dict(model_preset="llama_125m", max_len=256,
              prefill_buckets=(32,), decode_chunk=16, paged=True,
              block_size=_PAGED_BASE["block_size"],
              max_slots=_PAGED_BASE["max_slots"])
    kw.update(engine_kw or {})
    preset = getattr(llama.LlamaConfig, kw["model_preset"])
    cfg = preset(max_seq_len=kw["max_len"])
    bs = kw["block_size"]
    nb_bf16 = base_blocks or _PAGED_BASE["num_blocks"]
    pool_bytes = 2 * (nb_bf16 - 1) * cfg.n_layers * bs \
        * cfg.n_kv_heads * cfg.head_dim * 2
    nb_int8 = 1 + blocks_for_bytes(
        pool_bytes, cfg.n_layers, bs, cfg.n_kv_heads, cfg.head_dim,
        kv_quant="int8")
    scale = nb_int8 / nb_bf16
    bf16 = _serve_bench(n_requests, paged=True,
                        engine_kw={**kw, "num_blocks": nb_bf16},
                        suffix="_qbase", vocab=vocab)
    int8 = _serve_bench(
        n_requests, paged=True,
        engine_kw={**kw, "num_blocks": nb_int8, "kv_quant": "int8",
                   "max_slots": int(kw["max_slots"] * scale)},
        suffix="_int8", vocab=vocab)
    return {
        "kv_quant_blocks_bf16": nb_bf16,
        "kv_quant_blocks_int8": nb_int8,
        "serve_decode_tok_per_s_int8":
            int8["serve_decode_tok_per_s_int8"],
        "kv_quant_decode_ratio": round(
            int8["serve_decode_tok_per_s_int8"]
            / max(1e-9, bf16["serve_decode_tok_per_s_qbase"]), 2),
    }


def _prefix_cache_bench(n_requests: int = 96) -> dict:
    """COW prefix sharing: a fleet of requests sharing one 192-token
    system prompt (24 unique tail tokens each) vs the same fleet with
    fully unique prompts on the same engine shape.  The warm side
    prefills only its 24-token suffix against shared blocks, so the
    ratio isolates what the hash-trie prefix cache buys."""
    import numpy as np

    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer

    rng = np.random.default_rng(7)
    system = rng.integers(1, 32000, 192).tolist()

    def run_fleet(shared: bool) -> float:
        handle = serve.run(serve.deployment(LLMServer).bind(
            model_preset="llama_125m", max_slots=112, max_len=256,
            prefill_buckets=(32, 256), decode_chunk=16, paged=True,
            block_size=64))
        try:
            def req(i):
                tail = rng.integers(1, 32000, 24).tolist()
                prompt = (system + tail if shared
                          else rng.integers(1, 32000, 216).tolist())
                return {"prompt": prompt, "max_new_tokens": 32}

            handle.generate.remote(req(0)).result(timeout=600)  # warm
            t0 = time.perf_counter()
            for r in [handle.generate.remote(req(i))
                      for i in range(n_requests)]:
                r.result(timeout=600)
            return time.perf_counter() - t0
        finally:
            serve.shutdown()

    cold = run_fleet(shared=False)
    warm = run_fleet(shared=True)
    return {
        "prefix_cache_speedup": round(cold / warm, 2),
        "prefix_cache_cold_s": round(cold, 2),
        "prefix_cache_warm_s": round(warm, 2),
    }


def _disagg_bench(n_requests: int = 64) -> dict:
    """Prefill/decode disaggregation TTFT: one prefill + one decode
    replica (KV handoff over the shm ring on one host), driven at a
    steady rate; reports admitted p99 TTFT — the number disaggregation
    exists to protect (prefill never queues behind decode chunks)."""
    import threading

    import numpy as np

    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMServer

    handle = serve.run(serve.deployment(LLMServer, replica_roles={
        "prefill": 1, "decode": 1}).bind(
        model_preset="llama_125m", max_slots=112, max_len=256,
        prefill_buckets=(32,), decode_chunk=16, paged=True,
        block_size=64))
    try:
        rng = np.random.default_rng(3)

        def req():
            return {"prompt": rng.integers(1, 32000, 24).tolist(),
                    "max_new_tokens": 32}

        # Warm + measure unloaded completion rate to pace the run.
        t0 = time.perf_counter()
        for r in [handle.generate.remote(req()) for _ in range(16)]:
            r.result(timeout=600)
        cap_rps = 16 / (time.perf_counter() - t0)
        ttfts, errs = [], []
        threads = []

        def one():
            try:
                ttfts.append(handle.generate.remote(req()).result(
                    timeout=600)["ttft_ms"])
            except Exception as e:  # noqa: BLE001
                errs.append(e)
        for _ in range(n_requests):
            t = threading.Thread(target=one)
            t.start()
            threads.append(t)
            time.sleep(1.0 / cap_rps)
        for t in threads:
            t.join(timeout=600)
    finally:
        serve.shutdown()
    if not ttfts:
        raise RuntimeError(f"all disagg requests failed: {errs[:2]}")
    ttfts.sort()
    return {
        "disagg_ttft_p99_ms": round(
            ttfts[max(0, int(len(ttfts) * 0.99) - 1)], 1),
        "disagg_ttft_p50_ms": round(ttfts[len(ttfts) // 2], 1),
    }


def _object_plane_bench(size_bytes: int) -> dict:
    """Node-to-node primary-copy pull: a worker subprocess produces a
    big array (pinned as a primary on its node); the driver times the
    chunked materialization (pull_manager.h:52 analogue).  Loopback TCP
    bounds the absolute number; the point is the protocol overhead."""
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster.cluster_utils import Cluster

    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=2, resources={"holder": 1})
    c.connect(num_cpus=2)
    try:
        @ray_tpu.remote(resources={"holder": 1})
        def produce(n):
            rng = np.random.default_rng(0)
            return rng.integers(0, 255, n, dtype=np.uint8)

        ref = produce.remote(size_bytes)
        rt = ray_tpu.get_runtime()
        # Wait for the location record (production time excluded).
        obj = rt.object_store.wait_and_get(ref.object_id(), 300.0)
        assert obj.location is not None, "expected a primary-copy return"
        t0 = time.perf_counter()
        out = ray_tpu.get(ref, timeout=600)
        dt = time.perf_counter() - t0
        assert out.nbytes == size_bytes
        return {
            "object_pull_gbytes_per_s": round(size_bytes / dt / 1e9, 2),
            "object_pull_mb": size_bytes // (1024 * 1024),
        }
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def _shuffle_bench(n_blocks: int = 32, rows_per_block: int = 4096,
                   width: int = 256) -> dict:
    """Push-based shuffle exchange (data/exchange.py) vs the
    materialized baseline in the same run: ``random_shuffle`` streams
    partition fragments map→reduce over the shm rings as they are
    produced, while the baseline pulls every block to one place,
    permutes, and re-emits (the pre-push data path).  Local mode =
    same-host soak: all fragments should ride the shm transport —
    ``shuffle_shm_bytes`` being nonzero is part of the acceptance
    gate, not just the throughput ratio."""
    import numpy as np

    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.data.block import BlockAccessor
    from ray_tpu.data.executor import AllToAll
    from ray_tpu.observability.metrics import metrics_summary

    ray_tpu.shutdown()
    ray_tpu.init(num_tpus=0)
    try:
        rng = np.random.default_rng(0)
        blocks = []
        for i in range(n_blocks):
            blocks.append({
                "x": rng.standard_normal(
                    (rows_per_block, width)).astype(np.float32),
                "id": np.arange(i * rows_per_block,
                                (i + 1) * rows_per_block,
                                dtype=np.int64)})
        total_bytes = sum(b["x"].nbytes + b["id"].nbytes
                          for b in blocks)
        total_rows = n_blocks * rows_per_block
        ds = rd.from_blocks(blocks)

        def consume(dataset) -> float:
            t0 = time.perf_counter()
            rows = sum(b["x"].shape[0] for b in dataset.iter_blocks())
            dt = time.perf_counter() - t0
            assert rows == total_rows, (rows, total_rows)
            return dt

        shm0 = metrics_summary().get(
            "ray_tpu_shuffle_bytes", {}).get("shm", 0.0)
        push_dt = consume(ds.random_shuffle(seed=0))
        shm1 = metrics_summary().get(
            "ray_tpu_shuffle_bytes", {}).get("shm", 0.0)

        def mat_shuffle(blks, _ctx):
            # The materialized path: everything in one place first,
            # one global permutation, re-slice.
            whole = BlockAccessor.concat(blks)
            n = BlockAccessor.num_rows(whole)
            shuffled = BlockAccessor.take(
                whole, np.random.default_rng(0).permutation(n))
            bounds = np.linspace(0, n, max(1, len(blks)) + 1
                                 ).astype(np.int64)
            return [BlockAccessor.slice(shuffled, int(lo), int(hi))
                    for lo, hi in zip(bounds[:-1], bounds[1:])]

        mat_dt = consume(ds._with(
            AllToAll("MaterializedShuffle", mat_shuffle)))

        return {
            "shuffle_gbytes_per_s": round(
                total_bytes / push_dt / 1e9, 3),
            "shuffle_gbytes_per_s_materialized": round(
                total_bytes / mat_dt / 1e9, 3),
            "shuffle_push_speedup": round(mat_dt / push_dt, 2),
            "shuffle_mb": total_bytes // (1024 * 1024),
            "shuffle_shm_bytes": int(shm1 - shm0),
        }
    finally:
        ray_tpu.shutdown()


def _dag_roundtrip_bench(n_iters: int = 150) -> dict:
    """2-actor compiled-DAG ping-pong (64 KiB payload), actors in two
    worker processes on this host: per-pass round-trip latency with the
    native shm-channel transport vs the same plan forced onto the
    object plane (compiled_dag_node.py:691 aDAG data-plane payoff)."""
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.dag import InputNode

    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=2, resources={"d0": 10})
    c.add_node(num_cpus=2, resources={"d1": 10})
    c.connect(num_cpus=2)
    try:
        @ray_tpu.remote
        class Stage:
            def step(self, x):
                return x

        def run(**opts):
            payload = np.zeros(16384, dtype=np.float32)
            with InputNode() as inp:
                a = Stage.options(resources={"d0": 1}).bind()
                b = Stage.options(resources={"d1": 1}).bind()
                dag = b.step.bind(a.step.bind(inp))
            compiled = dag.experimental_compile(**opts)
            for _ in range(15):
                ray_tpu.get(compiled.execute(payload))
            t0 = time.perf_counter()
            for _ in range(n_iters):
                ray_tpu.get(compiled.execute(payload))
            dt = time.perf_counter() - t0
            used_channels = bool(compiled._channel_edges)
            compiled.teardown()
            return dt / n_iters * 1e6, used_channels

        chan_us, used = run()
        plane_us, _ = run(channel_transport=False)
        out = {"dag_roundtrip_object_plane_us": round(plane_us, 1)}
        if used:
            out["dag_roundtrip_us"] = round(chan_us, 1)
        else:  # channel lib unavailable: report the fallback number
            out["dag_roundtrip_us"] = round(plane_us, 1)
            out["dag_roundtrip_channel_unavailable"] = True
        return out
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def _dag_recovery_bench() -> dict:
    """Kill→first-successful-pass latency of the channel data plane's
    self-healing: a 2-actor compiled DAG's producer (max_restarts=1) is
    chaos-killed mid-pass; measures the wall time from the kill firing
    to the first subsequent pass completing on rebuilt rings (restart +
    ring teardown + replan + pass)."""
    import ray_tpu
    from ray_tpu.dag import InputNode
    from ray_tpu.exceptions import ActorDiedError, ChannelError
    from ray_tpu.experimental import chaos
    from ray_tpu.experimental.channel import channels_available

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4, num_tpus=0)
    try:
        if not channels_available():
            return {"dag_recovery_channel_unavailable": True}

        @ray_tpu.remote
        class Stage:
            def step(self, x):
                return x + 1

        with InputNode() as inp:
            a = Stage.options(max_restarts=1).bind()
            b = Stage.bind()
            dag = b.step.bind(a.step.bind(inp))
        compiled = dag.experimental_compile(channel_timeout=2.0)
        for _ in range(3):
            assert ray_tpu.get(compiled.execute(0)) == 2
        if not compiled._channel_edges:
            return {"dag_recovery_channel_unavailable": True}

        sched = chaos.schedule().kill_at_ring_write(
            "dag0-1", nth=4, no_restart=False)
        with sched:
            t0 = time.perf_counter()
            try:
                ray_tpu.get(compiled.execute(0), timeout=30.0)
            except (ActorDiedError, ChannelError):
                pass
            deadline = time.perf_counter() + 60.0
            while True:
                try:
                    assert ray_tpu.get(compiled.execute(0),
                                       timeout=10.0) == 2
                    break
                except (ActorDiedError, ChannelError):
                    if time.perf_counter() > deadline:
                        raise
                    time.sleep(0.05)
            dt = time.perf_counter() - t0
        assert sched.fired("ring_kill") == 1
        compiled.teardown()
        return {"dag_recovery_ms": round(dt * 1e3, 1)}
    finally:
        ray_tpu.shutdown()


def _paired_overhead_bench(module: str, pct_key: str, on_key: str,
                           off_key: str, n_pairs: int = 220) -> dict:
    """ONE harness for the <plane>_overhead_pct phases (tracing plane,
    log plane): the cross-process 2-actor compiled-DAG ping-pong from
    the roundtrip phase, measured in PAIRED adjacent passes with the
    named observability module (``enable()``/``disable()``) toggled
    cluster-wide between passes — driver-side directly, workers via a
    pinned remote task flipping their process-local flag.  The pass
    time is bimodal on shared CI (thread-scheduling regimes lasting
    seconds dwarf the plane's cost), so only back-to-back passes
    compare; the median per-pair ratio cancels the box's load drift,
    which is larger than the overhead itself.  Guard target for every
    phase built on this: <plane>_overhead_pct < 5."""
    import importlib

    import numpy as np

    import ray_tpu
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.dag import InputNode

    plane = importlib.import_module(module)
    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=2, resources={"d0": 10})
    c.add_node(num_cpus=2, resources={"d1": 10})
    c.connect(num_cpus=2)
    try:
        @ray_tpu.remote
        class Stage:
            def step(self, x):
                return x

        @ray_tpu.remote
        def set_plane(mod: str, on: bool):
            import importlib as il

            m = il.import_module(mod)
            m.enable() if on else m.disable()
            return on

        def toggle(on: bool):
            plane.enable() if on else plane.disable()
            ray_tpu.get([
                set_plane.options(resources={"d0": 1}).remote(
                    module, on),
                set_plane.options(resources={"d1": 1}).remote(
                    module, on)])

        payload = np.zeros(16384, dtype=np.float32)
        with InputNode() as inp:
            a = Stage.options(resources={"d0": 1}).bind()
            b = Stage.options(resources={"d1": 1}).bind()
            dag = b.step.bind(a.step.bind(inp))
        compiled = dag.experimental_compile()
        for _ in range(15):
            ray_tpu.get(compiled.execute(payload))

        def one_pass(on: bool) -> float:
            toggle(on)
            t0 = time.perf_counter()
            ray_tpu.get(compiled.execute(payload))
            return (time.perf_counter() - t0) * 1e6

        # PER-PASS adjacent pairs, order alternating within pairs;
        # toggles happen OUTSIDE the timed region.
        ratios: list = []
        on_samples: list = []
        off_samples: list = []
        try:
            for i in range(n_pairs):
                if i % 2 == 0:
                    on_b = one_pass(True)
                    off_b = one_pass(False)
                else:
                    off_b = one_pass(False)
                    on_b = one_pass(True)
                on_samples.append(on_b)
                off_samples.append(off_b)
                ratios.append(on_b / off_b)
        finally:
            toggle(True)
        compiled.teardown()
        # A pair straddling a scheduling-regime shift shows a 2-10x
        # ratio in either direction — that is the box, not the plane
        # (whose true cost is tens of µs on a multi-ms pass).  Trim
        # those artifacts, then take the median.
        kept = [r for r in ratios if 0.5 <= r <= 2.0] or ratios
        kept.sort()
        med_ratio = kept[len(kept) // 2]
        on_samples.sort()
        off_samples.sort()
        return {
            pct_key: round((med_ratio - 1.0) * 100.0, 2),
            on_key: round(on_samples[len(on_samples) // 2], 1),
            off_key: round(off_samples[len(off_samples) // 2], 1),
        }
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def _obs_overhead_bench(n_pairs: int = 220) -> dict:
    """Tracing/metrics-plane overhead on ``dag_roundtrip_us`` (guard:
    obs_overhead_pct < 5; measured ~1-4% on CI hardware)."""
    return _paired_overhead_bench(
        "ray_tpu.observability.tracing", "obs_overhead_pct",
        "obs_traced_roundtrip_us", "obs_untraced_roundtrip_us",
        n_pairs)


def _device_telemetry_overhead_bench(n_pairs: int = 220) -> dict:
    """Device-plane overhead on ``dag_roundtrip_us`` (guard:
    device_telemetry_overhead_pct < 5).  The plane's steady-state cost
    is the sampler tick (live-arrays walk / memory_stats) plus the
    per-hot-loop annotation probe; sampling is forced to 20 Hz
    cluster-wide (workers inherit the env) so the paired passes
    actually overlap sampler ticks — at the production 1 Hz default
    the phase would mostly measure nothing."""
    import os as _os

    prev = _os.environ.get("RAY_TPU_DEVICE_SAMPLE_S")
    _os.environ["RAY_TPU_DEVICE_SAMPLE_S"] = "0.05"
    try:
        return _paired_overhead_bench(
            "ray_tpu.observability.device",
            "device_telemetry_overhead_pct",
            "device_on_roundtrip_us", "device_off_roundtrip_us",
            n_pairs)
    finally:
        if prev is None:
            _os.environ.pop("RAY_TPU_DEVICE_SAMPLE_S", None)
        else:
            _os.environ["RAY_TPU_DEVICE_SAMPLE_S"] = prev


def _log_plane_overhead_bench(n_pairs: int = 220) -> dict:
    """Structured-log-plane overhead on ``dag_roundtrip_us``: each
    logged pass emits one driver dag record + per-task records on both
    workers and ships them on the EventShipper rails (guard:
    log_plane_overhead_pct < 5; measured ~1.4% on CI hardware)."""
    return _paired_overhead_bench(
        "ray_tpu.observability.logs", "log_plane_overhead_pct",
        "log_on_roundtrip_us", "log_off_roundtrip_us", n_pairs)


def _flightrec_overhead_bench(n_pairs: int = 220) -> dict:
    """Flight-recorder overhead on ``dag_roundtrip_us``: with the
    plane on, every process's snapshot thread drains new timeline
    events + log records to its on-disk ring at the flush cadence
    (forced to 50 ms cluster-wide so the paired passes actually
    overlap snapshot ticks; production default is 500 ms).  Guard:
    flightrec_overhead_pct < 5."""
    import os as _os

    prev = _os.environ.get("RAY_TPU_FLIGHTREC_FLUSH_S")
    _os.environ["RAY_TPU_FLIGHTREC_FLUSH_S"] = "0.05"
    try:
        return _paired_overhead_bench(
            "ray_tpu.observability.flightrec",
            "flightrec_overhead_pct",
            "flightrec_on_roundtrip_us", "flightrec_off_roundtrip_us",
            n_pairs)
    finally:
        if prev is None:
            _os.environ.pop("RAY_TPU_FLIGHTREC_FLUSH_S", None)
        else:
            _os.environ["RAY_TPU_FLIGHTREC_FLUSH_S"] = prev


def _tsdb_bench(n_nodes: int = 3, n_flushes: int = 120,
                n_queries: int = 50, n_pairs: int = 120) -> dict:
    """Metrics TSDB phases: ``metrics_query_us`` (end-to-end RPC
    latency of a windowed p99 + rate query against ingested history)
    and ``tsdb_ingest_overhead_pct`` (the paired-adjacent-trimmed-
    median method of the ``*_overhead_pct`` phases, applied at the
    ingest boundary: push_events with the TSDB enabled vs disabled —
    guard < 5%)."""
    import time as _time

    from ray_tpu.cluster.head import HeadServer
    from ray_tpu.cluster.rpc import RpcClient
    from ray_tpu.observability import tsdb as tsdb_mod

    def snapshot(node: str, n: int, ts: float) -> dict:
        # Shaped like a real export_state: a tagged counter family, a
        # gauge, and a multi-bucket histogram per node.
        return {"ts": ts, "incarnation": f"inc-{node}", "state": {
            "bench_requests": {
                "kind": "counter", "description": "",
                "tag_keys": ("where",),
                "values": {("ingress",): float(3 * n),
                           ("dispatch",): float(2 * n)}},
            "bench_depth": {
                "kind": "gauge", "description": "", "tag_keys": (),
                "values": {(): float(n % 17)}},
            "bench_latency": {
                "kind": "histogram", "description": "",
                "tag_keys": (), "values": {(): 0.05 * n},
                "boundaries": [0.001, 0.01, 0.1, 1.0, 10.0],
                "counts": {(): [n, 4 * n, 2 * n, n, 0, 0]}},
        }}

    # A realistic flush: the metrics snapshot rides ONE RPC with the
    # interval's timeline events + log records (the EventShipper
    # payload shape) — that whole ingest is the denominator the
    # overhead guard is about, not an empty ping.
    def flush_payload(node: str, n: int, ts: float) -> dict:
        return {
            "node_id": node, "pid": 1,
            "events": [{"name": "task::step", "ph": "X",
                        "pid": f"{node}-1", "tid": "main",
                        "ts": (ts + i * 1e-3) * 1e6, "dur": 800,
                        "args": {"trace_id": f"t{n}-{i}"}}
                       for i in range(150)],
            "logs": [{"msg": f"record {i}", "levelno": 20,
                      "level": "INFO", "logger": "bench",
                      "created": ts} for i in range(30)],
            "metrics": snapshot(node, n, ts), "flush_s": 1.0,
            "dropped": 0, "logs_dropped": 0}

    def push(cl, node, n, ts):
        cl.call("push_events", flush_payload(node, n, ts))

    head = HeadServer("127.0.0.1", 0)
    cl = RpcClient(head.address)
    try:
        t0 = _time.time() - n_flushes
        for i in range(n_flushes):
            for node in range(n_nodes):
                push(cl, f"node{node}", i, t0 + i)

        # --- metrics_query_us: median over p99-from-buckets and a
        # grouped rate (the two expensive evaluator paths).
        exprs = ["p99(bench_latency)[60s] by (node_id)",
                 "rate(bench_requests)[60s] by (node_id)"]
        lat: list = []
        for i in range(n_queries):
            expr = exprs[i % len(exprs)]
            q0 = _time.perf_counter()
            out = cl.call("metrics_query", {"expr": expr})
            lat.append((_time.perf_counter() - q0) * 1e6)
            assert out["rows"], "bench query returned no rows"
        lat.sort()

        # --- ingest overhead: paired adjacent push_events with the
        # TSDB toggled (head is in-process, so the module flag
        # applies), trimmed-median per-pair ratio like the other
        # overhead phases.
        ratios: list = []
        seq = n_flushes
        now = _time.time()
        try:
            for i in range(n_pairs):
                def one(on: bool) -> float:
                    tsdb_mod.enable() if on else tsdb_mod.disable()
                    p0 = _time.perf_counter()
                    push(cl, "node0", seq, now + 0.001 * seq)
                    return _time.perf_counter() - p0
                if i % 2 == 0:
                    on_c = one(True)
                    seq += 1
                    off_c = one(False)
                else:
                    off_c = one(False)
                    seq += 1
                    on_c = one(True)
                seq += 1
                ratios.append(on_c / off_c)
        finally:
            tsdb_mod.enable()
        kept = [r for r in ratios if 0.5 <= r <= 2.0] or ratios
        kept.sort()
        med = kept[len(kept) // 2]
        stats = cl.call("metrics_query", {"names": True})["stats"]
        return {
            "metrics_query_us": round(lat[len(lat) // 2], 1),
            "tsdb_ingest_overhead_pct": round((med - 1.0) * 100.0, 2),
            "tsdb_series": stats["series"],
            "tsdb_bytes_per_sample": round(
                stats["bytes"] / max(1, stats["ingested_samples"]), 2),
        }
    finally:
        cl.close()
        head.shutdown()


def _broadcast_bench(size_bytes: int, n_nodes: int = 3) -> dict:
    """Push-based broadcast tree (push_manager.h:30 analogue): driver
    fans one object out to ``n_nodes`` workers; aggregate GB/s =
    size * n / wall.  Loopback TCP bounds the absolute number."""
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.util import broadcast

    ray_tpu.shutdown()
    c = Cluster()
    for i in range(n_nodes):
        c.add_node(num_cpus=1, name=f"b{i}")
    c.connect(num_cpus=1)
    try:
        rng = np.random.default_rng(0)
        ref = ray_tpu.put(rng.integers(0, 255, size_bytes,
                                       dtype=np.uint8))
        t0 = time.perf_counter()
        n = broadcast(ref)
        dt = time.perf_counter() - t0
        assert n == n_nodes, f"broadcast reached {n}/{n_nodes}"
        return {
            "broadcast_gbytes_per_s": round(
                size_bytes * n_nodes / dt / 1e9, 2),
            "broadcast_nodes": n_nodes,
            "broadcast_mb": size_bytes // (1024 * 1024),
        }
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def _net_line_rate() -> float:
    """Single-stream line rate of the fabric this bench runs on (GB/s):
    one raw TCP stream, sendall → recv_into, 64 MB payload.  The
    device-broadcast acceptance bar is 'aggregate within 10x of this'
    — measuring it here makes the ratio portable across CI boxes (a
    2-core sandbox's loopback does ~0.6 GB/s; a real host does 6+)."""
    import socket
    import threading

    import numpy as np

    size = 64 * 1024 * 1024
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    data = np.random.default_rng(0).integers(0, 255, size, np.uint8)
    buf = np.empty(size, np.uint8)

    done = [0]

    def rx():
        conn, _ = srv.accept()
        with conn:
            view = memoryview(buf)
            got = 0
            while got < size:
                r = conn.recv_into(view[got:], size - got)
                if r == 0:
                    return  # peer closed early: leave done short
                got += r
            done[0] = got

    t = threading.Thread(target=rx, daemon=True)
    t.start()
    s = socket.create_connection(srv.getsockname())
    s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    t0 = time.perf_counter()
    s.sendall(memoryview(data))
    t.join(timeout=120)
    dt = time.perf_counter() - t0
    s.close()
    srv.close()
    if done[0] != size:
        # A failed probe must not yield a tiny 'line rate' that
        # inflates the broadcast ratio ~1000x and silently passes the
        # acceptance bar.
        raise RuntimeError(
            f"line-rate probe incomplete: {done[0]}/{size} bytes")
    return size / dt / 1e9


def _device_broadcast_bench(size_bytes: int, n_nodes: int = 3) -> dict:
    """Device-array broadcast: a ``jax.Array`` (bfloat16) payload rides
    the striped push tree natively — zero-copy dlpack export at the
    source, header-only metadata frame, ``device_put`` from the staging
    buffer at each recipient (docs/networking.md).  The acceptance bar
    is aggregate within 10x of single-stream line rate; the phase
    measures the fabric's own line rate so the ratio travels across
    hardware."""
    import jax.numpy as jnp
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.util import broadcast

    ray_tpu.shutdown()
    c = Cluster()
    for i in range(n_nodes):
        c.add_node(num_cpus=1, resources={f"db{i}": 1}, name=f"db{i}")
    c.connect(num_cpus=1)
    try:
        n_elems = size_bytes // 2  # bf16
        x = jnp.asarray(np.random.default_rng(0).standard_normal(
            n_elems, dtype=np.float32), dtype=jnp.bfloat16)
        ref = ray_tpu.put(x)  # seals: one device->host export
        t0 = time.perf_counter()
        n = broadcast(ref)
        dt = time.perf_counter() - t0
        assert n == n_nodes, f"device broadcast reached {n}/{n_nodes}"

        # Parity spot check on a recipient node: the pushed copy
        # rebuilds as a bf16 jax.Array of the right shape and values.
        @ray_tpu.remote(resources={f"db{n_nodes - 1}": 1})
        def probe(arr):
            import jax as _jax
            import jax.numpy as _jnp

            assert isinstance(arr, _jax.Array)
            assert arr.dtype == _jnp.bfloat16
            return int(arr.shape[0]), float(_jnp.asarray(
                arr[:1024], _jnp.float32).sum())

        shape0, csum = ray_tpu.get(probe.remote(ref), timeout=120)
        assert shape0 == n_elems
        ref_sum = float(jnp.asarray(x[:1024], jnp.float32).sum())
        assert abs(csum - ref_sum) <= max(1.0, abs(ref_sum)) * 0.01, \
            f"device broadcast parity: {csum} vs {ref_sum}"
        agg = size_bytes * n_nodes / dt / 1e9
        out = {
            "device_broadcast_gbytes_per_s": round(agg, 2),
            "device_broadcast_nodes": n_nodes,
            "device_broadcast_mb": size_bytes // (1024 * 1024),
        }
        try:
            line = _net_line_rate()
            out["net_line_rate_gbytes_per_s"] = round(line, 2)
            out["device_broadcast_line_rate_ratio"] = round(
                agg / line, 2)
        except Exception as e:  # noqa: BLE001 -- probe is best-effort
            out["net_line_rate_error"] = f"{type(e).__name__}: {e}"
        return out
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def _dcn_allreduce_bench(size_bytes: int, n_nodes: int = 3) -> dict:
    """Ring allreduce across ``n_nodes`` separate node processes: KV
    rendezvous through the head, raw-socket ring, reduce overlapping
    transfer (ray_tpu/collectives).  Reported as NCCL-convention bus
    bandwidth, ``2*(n-1)/n * size / wall``, with a built-in parity
    check vs the single-process sum."""
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster.cluster_utils import Cluster

    ray_tpu.shutdown()
    c = Cluster()
    for i in range(n_nodes):
        c.add_node(num_cpus=1, resources={f"ar{i}": 1}, name=f"ar{i}")
    c.connect(num_cpus=1)

    @ray_tpu.remote
    class Member:
        def __init__(self, rank, world):
            from ray_tpu.collectives import create_group

            self.group = create_group("bench-ar", rank, world,
                                      timeout=120)
            self.rank = rank

        def reduce(self, n_elems):
            import time as _t

            import numpy as _np

            x = _np.full(n_elems, float(self.rank + 1), _np.float32)
            t0 = _t.perf_counter()
            out = self.group.allreduce(x, "sum")
            return (_t.perf_counter() - t0,
                    float(out[0]), float(out[-1]))

        def close(self):
            self.group.close()

    try:
        members = [
            Member.options(resources={f"ar{i}": 1}).remote(i, n_nodes)
            for i in range(n_nodes)]
        # Warmup pass: ring links are already up (rendezvous in
        # __init__), this pages the numpy buffers + jit-warms chunking.
        ray_tpu.get([m.reduce.remote(4096) for m in members],
                    timeout=180)
        n_elems = size_bytes // 4  # f32
        outs = ray_tpu.get(
            [m.reduce.remote(n_elems) for m in members], timeout=600)
        # Slowest member's own op time — excludes RPC dispatch skew.
        wall = max(dt for dt, _, _ in outs)
        expect = n_nodes * (n_nodes + 1) / 2.0
        for _, first, last in outs:
            assert first == expect and last == expect, \
                f"allreduce parity: got ({first}, {last}), " \
                f"want {expect}"
        for m in members:
            m.close.remote()
        return {
            "dcn_allreduce_gbytes_per_s": round(
                2 * (n_nodes - 1) / n_nodes * size_bytes / wall / 1e9,
                2),
            "dcn_allreduce_nodes": n_nodes,
            "dcn_allreduce_mb": size_bytes // (1024 * 1024),
        }
    finally:
        ray_tpu.shutdown()
        c.shutdown()


def _overload_goodput_bench() -> dict:
    """Offered-load sweep (0.5× / 1× / 2× nominal capacity) against a
    2-replica deployment with bounded mailboxes and per-request
    deadlines: goodput, shed rate, and admitted-request p99 vs the
    deadline at each point.  The 2× point is the overload plane's
    headline — with admission control the system keeps serving at
    capacity and rejects the excess typed + fast, instead of melting
    into timeout soup."""
    import asyncio
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.exceptions import (BackPressureError,
                                    DeadlineExceededError)

    SERVICE_S = 0.05
    MAX_ONGOING = 4
    DEADLINE_S = 1.0

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8, num_tpus=0)

    @serve.deployment(name="ovl_bench", num_replicas=2,
                      max_ongoing_requests=MAX_ONGOING,
                      max_queued_requests=MAX_ONGOING)
    class Work:
        async def __call__(self, x):
            await asyncio.sleep(SERVICE_S)
            return x

    h = serve.run(Work.bind())
    try:
        for i in range(4):
            h.remote(i).result(timeout=30)
        t0 = time.perf_counter()
        for i in range(8):
            h.remote(i).result(timeout=30)
        svc = (time.perf_counter() - t0) / 8
        capacity = 2 * MAX_ONGOING / svc  # 2 replicas, req/s
        hd = h.options(deadline_s=DEADLINE_S)
        out = {"overload_capacity_rps": round(capacity, 1),
               "overload_deadline_s": DEADLINE_S}

        for factor in (0.5, 1.0, 2.0):
            offered = factor * capacity
            duration = 2.0
            lock = threading.Lock()
            oks, shed, lats = [], [], []

            def fire(tag):
                t_s = time.perf_counter()
                try:
                    hd.remote(tag).result()
                    with lock:
                        oks.append(tag)
                        lats.append(time.perf_counter() - t_s)
                except (BackPressureError, DeadlineExceededError):
                    with lock:
                        shed.append(tag)

            threads = []
            n = int(offered * duration)
            period = duration / max(1, n)
            t_start = time.perf_counter()
            for i in range(n):
                t = threading.Thread(target=fire, args=(i,),
                                     daemon=True)
                t.start()
                threads.append(t)
                time.sleep(period)
            for t in threads:
                t.join(timeout=DEADLINE_S + 5)
            wall = time.perf_counter() - t_start
            lats.sort()
            key = str(factor).replace(".", "_")
            out[f"overload_{key}x_goodput_rps"] = round(
                len(oks) / wall, 1)
            out[f"overload_{key}x_shed_rate"] = round(
                len(shed) / max(1, n), 3)
            out[f"overload_{key}x_admitted_p99_ms"] = round(
                lats[min(len(lats) - 1,
                         int(0.99 * len(lats)))] * 1000, 1) \
                if lats else None
        return out
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


def _head_scale_bench(sizes=(10, 100, 300),
                      duration_s: float = 4.0) -> dict:
    """Control-plane scale (ROADMAP item 5's named bench): mixed
    register/heartbeat/place/kv workload against a live subprocess
    head from the virtual-cluster harness, reported at 10/100/300
    virtual nodes — ``head_ops_per_s_<n>`` plus placement latency
    percentiles.  Heartbeats ride the delta-compressed batch protocol,
    mutations the journaled path; the head's persistence cost is
    isolated by `_head_persist_bench` below."""
    import os
    import tempfile

    from tools.vcluster import VCluster

    out = {}
    for n in sizes:
        storage = os.path.join(
            tempfile.mkdtemp(prefix="bench-vc-"), "head.bin")
        vc = VCluster(n, storage=storage, lease_ttl_s=5.0,
                      hb_interval_s=0.5)
        try:
            vc.start()
            t0 = time.perf_counter()
            vc.load(duration_s, threads=8)
            vc.join_load(timeout_s=duration_s + 60)
            dt = time.perf_counter() - t0
            st = vc.stats()
            assert st["stale_epoch_accepted"] == 0
            out[f"head_ops_per_s_{n}"] = round(st["ops_ok"] / dt, 1)
            out[f"placement_latency_p50_ms_{n}"] = \
                st["placement_p50_ms"]
            out[f"placement_latency_p99_ms_{n}"] = \
                st["placement_p99_ms"]
        finally:
            vc.stop()
    return out


def _head_failover_bench(n_nodes: int = 300,
                         duration_s: float = 4.0) -> dict:
    """Replicated-head phases (ROADMAP item 3 / ISSUE 12 acceptance):

    - ``head_ops_per_s_300_with_standby`` — mixed-op throughput at
      300 virtual nodes with a SYNC-mode hot standby attached (every
      mutation ack waits for standby durability): the replication
      overhead guard, compared against the standby-less
      ``head_ops_per_s_300`` from `_head_scale_bench`.
    - ``head_failover_unavailability_ms`` — the goodput dip across a
      primary kill -9 mid-load: largest gap between consecutive
      successful ops around the kill (promotion on the lapsed
      primary lease + client head-set failover inside it).
    """
    import os
    import tempfile

    from tools.vcluster import VCluster

    out = {}
    storage = os.path.join(
        tempfile.mkdtemp(prefix="bench-vc-ha-"), "head.bin")
    vc = VCluster(n_nodes, storage=storage, lease_ttl_s=5.0,
                  hb_interval_s=0.5)
    vc.primary_ttl_s = 1.0
    try:
        vc.start()
        # Phase 0: standby-less baseline in the SAME run — the
        # overhead ratio must not compare across bench phases
        # minutes apart (run-to-run swing on a loaded 1-core CI box
        # exceeds the overhead itself).
        t0 = time.perf_counter()
        vc.load(duration_s, threads=8)
        vc.join_load(timeout_s=duration_s + 60)
        dt0 = time.perf_counter() - t0
        with vc._lock:
            ok0 = sum(1 for _t, ok in vc.op_events if ok)
        vc.start_standby()
        # Phase 1: steady state with the sync standby attached.
        t0 = time.perf_counter()
        vc.load(duration_s, threads=8)
        vc.join_load(timeout_s=duration_s + 60)
        dt = time.perf_counter() - t0
        with vc._lock:
            ok1 = sum(1 for _t, ok in vc.op_events if ok) - ok0
        out["head_ops_per_s_300_with_standby"] = round(ok1 / dt, 1)
        out["head_repl_overhead_ratio"] = round(
            (ok1 / dt) / max(1e-9, ok0 / dt0), 3)
        # Phase 2: the failover dip.
        vc.load(duration_s + 4.0, threads=8)
        time.sleep(2.0)
        vc.kill_head()
        vc.wait_promoted(timeout_s=60.0)
        vc.join_load(timeout_s=duration_s + 120)
        # Settle before the ledger check: a node mid-death-and-
        # re-register would mis-classify its (legitimately dropped)
        # actors as lost.
        vc.wait_converged(timeout_s=60.0)
        report = vc.verify()
        assert report["missing"] == [], \
            f"failover lost {len(report['missing'])} acked mutations"
        assert report["stale_epoch_accepted"] == 0
        out["head_failover_unavailability_ms"] = \
            vc.unavailability_ms()
    finally:
        vc.stop()
    return out


def _head_persist_bench(n_ops: int = 400,
                        table_entries: int = 1500) -> dict:
    """Per-mutation persistence cost, journal WAL vs the seed's
    full-snapshot-per-mutation baseline, at a realistic table size
    (the snapshot cost is O(tables), the journal cost O(1) — the gap
    is the point of PR 8's durability move)."""
    import os
    import tempfile

    from ray_tpu.cluster.head import HeadServer
    from ray_tpu.cluster.rpc import RpcClient

    out = {}
    for mode in ("journal", "snapshot"):
        d = tempfile.mkdtemp(prefix=f"bench-head-{mode}-")
        head = HeadServer(storage_path=os.path.join(d, "gcs.bin"),
                          persist_mode=mode)
        cl = RpcClient(head.address)
        try:
            # Seeding doubles as fs-cache warmup; the snapshot mode's
            # cost scales with this table size, the journal's doesn't.
            for i in range(table_entries):
                cl.call("kv_put", {"key": f"seed{i}",
                                   "value": "x" * 64})
            # Best-of-2 reps: fsync latency on shared CI storage is
            # noisy enough to invert a 2x gap in a single shot.
            best = None
            for rep in range(2):
                t0 = time.perf_counter()
                for i in range(n_ops):
                    cl.call("kv_put", {"key": f"op{rep}-{i}",
                                       "value": "x" * 64})
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            out[f"head_persist_{mode}_us"] = round(
                best / n_ops * 1e6, 1)
        finally:
            cl.close()
            head.shutdown()
    out["head_persist_speedup"] = round(
        out["head_persist_snapshot_us"]
        / max(1e-9, out["head_persist_journal_us"]), 1)
    return out


def _raylint_bench() -> dict:
    """Static-analysis cost tracking: whole-package raylint wall clock
    (cold parse vs warm = AST-memo-served) plus the parse-cache hit
    rate, so the analysis stays honest against its 10 s gate as rules
    accumulate across PRs."""
    from ray_tpu.tools import raylint
    from ray_tpu.tools.raylint.model import _ParseCache

    root = raylint.default_package_root()
    _ParseCache._memo.clear()
    _ParseCache.reset_stats()
    t0 = time.perf_counter()
    findings = raylint.run_lint(root, use_baseline=False)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    raylint.run_lint(root, use_baseline=False)
    warm = time.perf_counter() - t0
    stats = _ParseCache.stats()
    total = stats["hits"] + stats["misses"]
    return {
        "raylint_wall_clock_s": round(cold, 3),
        "raylint_warm_wall_clock_s": round(warm, 3),
        "raylint_parse_cache_hit_rate": round(
            stats["hits"] / total, 3) if total else 0.0,
        "raylint_findings": len(findings),
    }


def main():
    import jax

    from ray_tpu.compile_cache import place_compile_cache
    from ray_tpu.models import llama

    place_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip; jax found platform "
            f"{platform!r}.  A CPU timing is not a speed number.")

    # 440M-param Llama, Pallas flash attention, head_dim 128 (full
    # MXU depth + exact (8,128) tiling — see llama_440m docstring),
    # remat_policy="attn" (backward reuses saved attention
    # residuals).  batch 8: 12/16 OOM with the saved residuals on
    # 16 GB HBM.
    cfg = llama.LlamaConfig.llama_440m()
    batch, seq, steps, warmup = 8, 2048, 10, 3

    import numpy as np

    import ray_tpu
    from ray_tpu import data as rd

    print("bench: train phase start", file=sys.stderr, flush=True)
    # fused=True: single-pass AdamW (train/optim.py) — same math as
    # the optax chain (loss-parity gated in tier-1), ~6 param-tree HBM
    # passes less per step in the optimizer slice (profile_mfu.py
    # opt_pct_of_step measures the win).
    state = llama.init_train_state(jax.random.key(0), cfg, fused=True)
    step = llama.make_train_step(cfg, fused=True)

    # Train through the real input plane: a ray_tpu.data pipeline
    # streams token blocks through the executor, batches them, and
    # device_puts each batch one step ahead of the consumer.
    ray_tpu.init(num_tpus=0)
    rng = np.random.default_rng(0)
    n_rows = (warmup + steps) * batch
    rows = rng.integers(0, cfg.vocab_size,
                        (n_rows, seq)).astype(np.int32)
    ds = rd.from_blocks(
        [{"tokens": rows[i:i + batch]}
         for i in range(0, n_rows, batch)])

    it = ds.iter_batches(batch_size=batch, drop_last=True,
                         prefetch_batches=2, device_put=True)
    for _ in range(warmup):
        state, metrics = step(state, next(it))
    jax.block_until_ready(metrics)

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, next(it))
    # Steps chain through `state`, so the last step's outputs being
    # ready means the whole sequence ran.
    jax.block_until_ready((state, metrics))
    dt = time.perf_counter() - t0
    ray_tpu.shutdown()

    tokens_per_step = batch * (seq - 1)
    tps = tokens_per_step * steps / dt

    n_params = llama.param_count(
        jax.eval_shape(lambda: llama.init_params(jax.random.key(0), cfg)))
    flops_per_tok = 6 * n_params  # dense-LM training approximation
    mfu_denom = _peak_bf16_flops(jax.devices()[0].device_kind)
    extra = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "model_params": int(n_params),
        "batch": batch,
        "seq": seq,
        "loss": float(metrics["loss"]),
    }
    extra["mfu"] = round(tps * flops_per_tok / mfu_denom, 4)

    # Serve north-star (BASELINE.md): req/s + p50 TTFT from the
    # continuous-batched decode deployment, on the same chip after
    # the train state is freed.  A failure must not cost the other
    # phases' numbers, but it does fail the run (exit status below).
    del state
    print("bench: serve phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_serve_bench())
    except Exception as e:  # noqa: BLE001
        extra["serve_error"] = f"{type(e).__name__}: {e}"

    print("bench: paged serve phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_serve_bench(paged=True))
        if "serve_decode_tok_per_s" in extra:
            extra["paged_vs_dense_decode_ratio"] = round(
                extra["serve_decode_tok_per_s_paged"]
                / extra["serve_decode_tok_per_s"], 2)
    except Exception as e:  # noqa: BLE001
        extra["serve_paged_error"] = f"{type(e).__name__}: {e}"

    print("bench: spec decode phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_serve_bench(
            paged=True, engine_kw=dict(_SPEC_ENGINE),
            suffix="_spec"))
        if "serve_decode_tok_per_s_paged" in extra:
            extra["spec_vs_paged_decode_ratio"] = round(
                extra["serve_decode_tok_per_s_spec"]
                / extra["serve_decode_tok_per_s_paged"], 2)
    except Exception as e:  # noqa: BLE001
        extra["spec_decode_error"] = f"{type(e).__name__}: {e}"

    print("bench: spec+int8 decode phase start", file=sys.stderr,
          flush=True)
    try:
        # The headline end-to-end number: spec decode + int8 KV
        # (2x block capacity at the paged pool's bytes) vs the
        # PR 10 paged baseline — the ≥2x acceptance bar.
        from ray_tpu.serve.kv_cache import blocks_for_bytes
        from ray_tpu.models import llama as _llama

        _c = _llama.LlamaConfig.llama_125m(max_seq_len=256)
        _bs = _PAGED_BASE["block_size"]
        _nbq = 1 + blocks_for_bytes(
            _paged_base_pool_bytes(_c), _c.n_layers, _bs,
            _c.n_kv_heads, _c.head_dim, kv_quant="int8")
        _scale = _nbq / _PAGED_BASE["num_blocks"]
        extra.update(_serve_bench(
            paged=True,
            engine_kw=dict(
                _SPEC_ENGINE, kv_quant="int8", num_blocks=_nbq,
                max_slots=int(_PAGED_BASE["max_slots"] * _scale)),
            suffix="_spec_int8"))
        if "serve_decode_tok_per_s_paged" in extra:
            extra["spec_int8_vs_paged_decode_ratio"] = round(
                extra["serve_decode_tok_per_s_spec_int8"]
                / extra["serve_decode_tok_per_s_paged"], 2)
    except Exception as e:  # noqa: BLE001
        extra["spec_int8_error"] = f"{type(e).__name__}: {e}"

    print("bench: kv quant phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_kv_quant_bench())
    except Exception as e:  # noqa: BLE001
        extra["kv_quant_error"] = f"{type(e).__name__}: {e}"

    print("bench: prefix cache phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_prefix_cache_bench())
    except Exception as e:  # noqa: BLE001
        extra["prefix_cache_error"] = f"{type(e).__name__}: {e}"

    print("bench: disagg phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_disagg_bench())
    except Exception as e:  # noqa: BLE001
        extra["disagg_error"] = f"{type(e).__name__}: {e}"

    print("bench: object plane phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_object_plane_bench(
            1024 * 1024 * 1024))
    except Exception as e:  # noqa: BLE001
        extra["object_pull_error"] = f"{type(e).__name__}: {e}"

    print("bench: shuffle phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_shuffle_bench(
            64, 16384, 256))
    except Exception as e:  # noqa: BLE001
        extra["shuffle_error"] = f"{type(e).__name__}: {e}"

    print("bench: broadcast phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_broadcast_bench(
            256 * 1024 * 1024))
    except Exception as e:  # noqa: BLE001
        extra["broadcast_error"] = f"{type(e).__name__}: {e}"

    print("bench: device broadcast phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_device_broadcast_bench(
            256 * 1024 * 1024))
    except Exception as e:  # noqa: BLE001
        extra["device_broadcast_error"] = f"{type(e).__name__}: {e}"

    print("bench: dcn allreduce phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_dcn_allreduce_bench(
            256 * 1024 * 1024))
    except Exception as e:  # noqa: BLE001
        extra["dcn_allreduce_error"] = f"{type(e).__name__}: {e}"

    print("bench: dag roundtrip phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_dag_roundtrip_bench())
    except Exception as e:  # noqa: BLE001
        extra["dag_roundtrip_error"] = f"{type(e).__name__}: {e}"

    print("bench: dag recovery phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_dag_recovery_bench())
    except Exception as e:  # noqa: BLE001
        extra["dag_recovery_error"] = f"{type(e).__name__}: {e}"

    print("bench: obs overhead phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_obs_overhead_bench())
    except Exception as e:  # noqa: BLE001
        extra["obs_overhead_error"] = f"{type(e).__name__}: {e}"

    print("bench: log plane overhead phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_log_plane_overhead_bench())
    except Exception as e:  # noqa: BLE001
        extra["log_plane_overhead_error"] = f"{type(e).__name__}: {e}"

    print("bench: device telemetry overhead phase start",
          file=sys.stderr, flush=True)
    try:
        extra.update(_device_telemetry_overhead_bench())
    except Exception as e:  # noqa: BLE001
        extra["device_telemetry_overhead_error"] = \
            f"{type(e).__name__}: {e}"

    print("bench: flightrec overhead phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_flightrec_overhead_bench())
    except Exception as e:  # noqa: BLE001
        extra["flightrec_overhead_error"] = f"{type(e).__name__}: {e}"

    print("bench: tsdb phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_tsdb_bench())
    except Exception as e:  # noqa: BLE001
        extra["tsdb_error"] = f"{type(e).__name__}: {e}"

    print("bench: overload goodput phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_overload_goodput_bench())
    except Exception as e:  # noqa: BLE001
        extra["overload_goodput_error"] = f"{type(e).__name__}: {e}"

    print("bench: head scale phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_head_scale_bench())
    except Exception as e:  # noqa: BLE001
        extra["head_scale_error"] = f"{type(e).__name__}: {e}"

    print("bench: head failover phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_head_failover_bench())
    except Exception as e:  # noqa: BLE001
        extra["head_failover_error"] = f"{type(e).__name__}: {e}"

    print("bench: head persistence phase start", file=sys.stderr,
          flush=True)
    try:
        extra.update(_head_persist_bench())
    except Exception as e:  # noqa: BLE001
        extra["head_persist_error"] = f"{type(e).__name__}: {e}"

    print("bench: raylint phase start", file=sys.stderr, flush=True)
    try:
        extra.update(_raylint_bench())
    except Exception as e:  # noqa: BLE001
        extra["raylint_error"] = f"{type(e).__name__}: {e}"

    print(json.dumps({
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": None,
        **extra,
    }))
    failed = sorted(k for k in extra if k.endswith("_error"))
    if failed:
        print(f"bench: FAILED phases: {', '.join(failed)}",
              file=sys.stderr, flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Device-plane telemetry: the accelerator half of the observability
story.

The host tiers (tracing/timeline, logs/profiling, TSDB/alerts) watch
the *framework*; this module watches the *chips* it exists to drive —
the signals MegaScale-style production diagnosis and Pathways-scale
scheduling decisions read.  Five surfaces, one module:

1. **HBM sampler** — a per-process daemon thread enumerates the local
   JAX devices every ``RAY_TPU_DEVICE_SAMPLE_S`` seconds and sets the
   ``ray_tpu_device_hbm_bytes_{used,peak,limit}`` /
   ``ray_tpu_device_live_buffers`` gauges, which ride the existing
   EventShipper flushes into the head TSDB (``last(...) by (node_id)``
   answers "how much HBM does each node hold RIGHT NOW").  On TPU the
   numbers come from ``device.memory_stats()`` (PJRT allocator stats);
   on the CPU backend — where tier-1 runs — a live-arrays fallback
   attributes ``jax.live_arrays()`` bytes per device, so the whole
   pipeline (sampler → gauges → shipper → TSDB → query/alert) is
   exercised without a chip.  The sampler NEVER imports jax and
   NEVER initialises a backend: it idles until the program has done
   both itself, so non-jax workers pay one sleeping thread, and a
   process that imports jax but leaves the chip to a child (or has yet
   to call ``jax.distributed.initialize``) is left alone.

2. **XLA compile tracking** — ``jax.monitoring`` listeners keep what
   jax times of a jitted function's first call: the Python trace, the
   lowering to MLIR and the backend compilation (a fetch from the
   persistent cache included) as timeline spans ``xla_trace`` /
   ``xla_lower`` / ``xla_compile`` on this process's lane, each with
   jax's own start and end and its ``fun_name``, stamped with the
   ambient trace id and parented to the ambient span; ``xla_compile``
   says whether the cache answered (``cache_hit``) and what the fetch
   took (``cache_fetch_s``).  Series: ``ray_tpu_xla_compiles_total``
   and ``ray_tpu_xla_compile_seconds`` count the BACKEND's part alone,
   as they always have (the recompile alert and the benchmark's
   ``setup_compile_s`` read them), and
   ``ray_tpu_xla_phase_seconds{phase=trace|lower|cache_fetch}`` the
   host's parts beside it — where a start goes once every executable
   is cached.  The shipped ``xla-recompile-storm`` default alert
   (observability/alerts.py) fires on a sustained compile rate — the
   "my bucketing is churning shapes" failure mode that silently turns
   a serving replica into a compile farm.

3. **Device-trace capture** — :func:`capture_device_trace` drives
   ``jax.profiler.start_trace``/``stop_trace`` and zips the resulting
   TensorBoard-loadable bundle (xplane.pb + trace.json.gz) into one
   artifact; the node RPC ``device_trace`` (cluster/client.py) runs it
   remotely and ships the artifact to the head's bounded store, where
   ``ray_tpu profile --device`` and ``/api/profile?device=1`` fetch
   it.  :func:`annotation` stamps host-side hot loops (train step,
   serve decode chunk) with ``jax.profiler.TraceAnnotation`` carrying
   the ambient trace id, so a device trace correlates with the cluster
   timeline by id.

4. **Model-plane series** — :func:`record_train_step` (per-step
   tokens/s + MFU from the train loop) and :func:`record_program_ema`
   (the serve engine's per-program execution-time EMAs) make the
   model plane's numbers continuously queryable; ``ray_tpu top``
   renders them live.

5. **Device seconds by scope** — a device trace names a fused op
   ``%fusion.362``; the program's own name for that work (the
   ``jax.named_scope`` it was traced under: ``ffn``, ``qkv_proj``,
   ``optimizer``) reaches only the ``op_name`` metadata of the compiled
   instruction, which the trace does not carry.  So the program keeps
   the map: :func:`register_program` remembers a hot-path jitted
   program with the shapes of one call (the train step at its first
   dispatch, the serve engine's programs at warm-up; only while
   tracing is enabled), :func:`program_scopes` lowers each once,
   through the compile cache, and reads ``instruction -> (scope,
   phase)`` off the compiled text (:func:`scope_of`, :data:`SCOPES`),
   and :func:`capture_device_trace` puts that map into its bundle as
   ``scopes.json``.  Never on a launch, harvest or start path.

``disable()`` turns sampling, the compile listener, and annotations
into no-ops.

Env knobs:
  RAY_TPU_DEVICE_TELEMETRY       0 disables the whole plane
  RAY_TPU_DEVICE_SAMPLE_S        sampler period (default 1.0)
  RAY_TPU_DEVICE_HBM_LIMIT_BYTES fallback per-device limit when the
                                 backend reports none (CPU; 0 = unknown)
"""

from __future__ import annotations

import contextlib
import io
import os
import re
import sys
import threading
import time
from typing import Any, Dict, List, Optional

_enabled = os.environ.get("RAY_TPU_DEVICE_TELEMETRY", "1").lower() \
    not in ("0", "false", "off")

DEFAULT_SAMPLE_S = 1.0


def _sample_period() -> float:
    """Sampler period, re-read per tick: tests and the overhead bench
    retune RAY_TPU_DEVICE_SAMPLE_S on a process whose sampler thread
    already runs."""
    try:
        return max(0.02, float(os.environ.get(
            "RAY_TPU_DEVICE_SAMPLE_S", DEFAULT_SAMPLE_S)))
    except ValueError:
        return DEFAULT_SAMPLE_S

# Fallback per-device byte limit for backends whose memory_stats() is
# unavailable (CPU): lets the hbm-pressure alert and the utilization
# gauge be exercised in tier-1 by pointing the knob at a small number.
_FALLBACK_LIMIT = int(os.environ.get(
    "RAY_TPU_DEVICE_HBM_LIMIT_BYTES", "0"))


def enabled() -> bool:
    return _enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    """No-op the plane (bench: measures its cost paired on/off)."""
    global _enabled
    _enabled = False


def _device_metrics():
    from . import metrics as _metrics

    return _metrics.metric_group("device", lambda: {
        "hbm_used": _metrics.Gauge(
            "ray_tpu_device_hbm_bytes_used",
            "accelerator memory in use (device.memory_stats on TPU; "
            "live-array bytes on the CPU fallback)",
            tag_keys=("device",)),
        "hbm_peak": _metrics.Gauge(
            "ray_tpu_device_hbm_bytes_peak",
            "peak accelerator memory in use since process start",
            tag_keys=("device",)),
        "hbm_limit": _metrics.Gauge(
            "ray_tpu_device_hbm_bytes_limit",
            "accelerator memory capacity (0 when the backend reports "
            "none and no fallback limit is configured)",
            tag_keys=("device",)),
        "hbm_util": _metrics.Gauge(
            "ray_tpu_device_hbm_utilization",
            "used / limit (only exported when the limit is known) — "
            "the hbm-pressure default alert reads this",
            tag_keys=("device",)),
        "live_buffers": _metrics.Gauge(
            "ray_tpu_device_live_buffers",
            "live device buffers (allocator count on TPU; live "
            "jax.Array count on the CPU fallback)",
            tag_keys=("device",)),
        "xla_compiles": _metrics.Counter(
            "ray_tpu_xla_compiles_total",
            "XLA backend compilations observed via jax.monitoring "
            "(a sustained rate is a recompilation storm)",
            tag_keys=("kind",)),
        "xla_compile_seconds": _metrics.Histogram(
            "ray_tpu_xla_compile_seconds",
            "XLA backend compilation wall time",
            boundaries=[0.01, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0, 600.0]),
        "xla_phase_seconds": _metrics.Histogram(
            "ray_tpu_xla_phase_seconds",
            "host time of a jitted function's first call, by phase: "
            "trace (Python -> jaxpr, nested jits inside their caller's), "
            "lower (jaxpr -> MLIR), cache_fetch (the persistent compile "
            "cache's answer, part of the backend's seconds)",
            boundaries=[0.01, 0.1, 0.5, 1.0, 5.0, 30.0, 120.0, 600.0],
            tag_keys=("phase",)),
    })


def model_plane_metrics():
    """The model-plane gauges (train step + serve engine hot loops)."""
    from . import metrics as _metrics

    return _metrics.metric_group("model_plane", lambda: {
        "train_tokens_per_s": _metrics.Gauge(
            "ray_tpu_train_tokens_per_s",
            "per-step training throughput (tokens processed / step "
            "wall time), set by the train hot loop every step"),
        "train_mfu": _metrics.Gauge(
            "ray_tpu_train_mfu",
            "per-step model FLOP/s utilization (6N approximation "
            "against the chip's bf16 roofline; only exported where "
            "the roofline is known — not on CPU)"),
        "train_step_seconds": _metrics.Gauge(
            "ray_tpu_train_step_seconds",
            "last training step wall time"),
        "train_expert_load_imbalance": _metrics.Gauge(
            "ray_tpu_train_expert_load_imbalance",
            "last read training step's busiest expert's tokens / the mean "
            "an expert, the worst expert layer (1.0: even routing)"),
        "train_router_bias_max": _metrics.Gauge(
            "ray_tpu_train_router_bias_max",
            "largest magnitude of the routers' selection biases after the "
            "last read training step's balance update"),
        "train_dispatch_compact_share": _metrics.Gauge(
            "ray_tpu_train_dispatch_compact_share",
            "share of the last read training step's expert layers whose "
            "dispatch was one block of moe.compact_rows sorted rows (1.0: "
            "every layer's held experts' rows fitted it)"),
        "program_ema": _metrics.Gauge(
            "ray_tpu_serve_program_seconds",
            "serve engine per-program execution-time EMA (prefill / "
            "decode_chunk / spec_round)",
            tag_keys=("deployment", "program")),
    })


def peak_bf16_flops(device_kind: str) -> Optional[float]:
    """Per-chip bf16 peak by device kind (public TPU spec sheets).
    None for a CPU kind — callers skip the MFU gauge there.  Any other
    kind missing from the table raises: a guessed peak turns into a
    wrong utilization that looks measured."""
    kind = (device_kind or "").lower()
    if not kind or "cpu" in kind:
        return None
    table = [
        ("v6", 918e12),          # Trillium / v6e
        ("v5 lite", 197e12),     # v5e (394 is the int8 number)
        ("v5litepod", 197e12),
        ("v5e", 197e12),
        ("v5p", 459e12),
        ("v4", 275e12),
        ("v3", 123e12),
        ("v2", 46e12),
    ]
    for key, flops in table:
        if key in kind:
            return flops
    raise ValueError(
        f"no bf16 peak on record for device kind {device_kind!r}; add "
        f"it to peak_bf16_flops with its source")


# ------------------------------------------------------------- sampler

# Peak tracking for the live-arrays fallback (memory_stats carries its
# own peak; the fallback must remember the high-water mark itself).
_fallback_peak: Dict[str, int] = {}


def initialized_jax():
    """The jax module iff this process imported it AND has already
    initialised a backend; else None.  Telemetry only ever looks at a
    backend the program brought up itself: ``jax.local_devices()`` on
    an uninitialised process would take the chip (one process per
    chip — a parent that leaves it to a child must stay off it) and
    would break a later ``jax.distributed.initialize``.  jax has no
    public probe for this; ``xla_bridge.backends_are_initialized`` is
    what ``jax.distributed`` itself checks."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge

    return jax if xla_bridge.backends_are_initialized() else None


def sample_devices() -> Optional[List[Dict[str, Any]]]:
    """One sample of every local device: ``{device, platform, used,
    peak, limit, live_buffers}`` per device.  Returns None until the
    program has initialised a jax backend (see initialized_jax)."""
    jax = initialized_jax()
    if jax is None:
        return None
    devices = jax.local_devices()
    stats_by_dev = {}
    for dev in devices:
        try:
            stats_by_dev[dev] = dev.memory_stats()
        except Exception:
            stats_by_dev[dev] = None
    if any(s is None for s in stats_by_dev.values()):
        fallback = _live_array_bytes(jax)
    else:
        fallback = {}
    out = []
    for dev in devices:
        name = str(dev)
        stats = stats_by_dev[dev]
        if stats:
            used = int(stats.get("bytes_in_use", 0))
            peak = int(stats.get("peak_bytes_in_use", used))
            limit = int(stats.get("bytes_limit")
                        or stats.get("bytes_reservable_limit") or 0)
            bufs = int(stats.get("num_allocs", 0))
        else:
            used, bufs = fallback.get(name, (0, 0))
            peak = max(_fallback_peak.get(name, 0), used)
            _fallback_peak[name] = peak
            limit = _FALLBACK_LIMIT
        out.append({"device": name, "platform": dev.platform,
                    "used": used, "peak": peak, "limit": limit,
                    "live_buffers": bufs})
    return out


def _live_array_bytes(jax) -> Dict[str, tuple]:
    """{device name: (bytes, count)} attributed from jax.live_arrays()
    — the CPU-backend stand-in for allocator stats.  Multi-device
    (sharded) arrays split their bytes evenly across holders."""
    acc: Dict[str, List[int]] = {}
    try:
        arrays = jax.live_arrays()
    except Exception:
        return {}
    for arr in arrays:
        try:
            devs = list(arr.devices())
            per = int(arr.nbytes) // max(1, len(devs))
        except Exception:
            continue
        for d in devs:
            slot = acc.setdefault(str(d), [0, 0])
            slot[0] += per
            slot[1] += 1
    return {k: (v[0], v[1]) for k, v in acc.items()}


def sample_once() -> Optional[List[Dict[str, Any]]]:
    """Sample and publish the device gauges (one sampler tick).  The
    compile listener installs as soon as jax is imported (registering
    it touches no backend), so compiles are counted from the first
    tick; the gauges wait for the program's own backend."""
    if not _enabled:
        return None
    install_compile_listener()
    samples = sample_devices()
    if samples is None:
        return None
    m = _device_metrics()
    for s in samples:
        tags = {"device": s["device"]}
        m["hbm_used"].set(float(s["used"]), tags=tags)
        m["hbm_peak"].set(float(s["peak"]), tags=tags)
        m["hbm_limit"].set(float(s["limit"]), tags=tags)
        m["live_buffers"].set(float(s["live_buffers"]), tags=tags)
        if s["limit"] > 0:
            m["hbm_util"].set(s["used"] / s["limit"], tags=tags)
    return samples


_sampler_lock = threading.Lock()
_sampler_stop: Optional[threading.Event] = None


def install() -> None:
    """Start the per-process sampler thread (idempotent; called at
    Runtime boot next to the structured-log handler).  The thread
    no-ops until the program initialises a jax backend, so boot stays
    jax-free and never takes the chip."""
    global _sampler_stop
    with _sampler_lock:
        if _sampler_stop is not None:
            return
        _sampler_stop = threading.Event()
        t = threading.Thread(target=_sampler_loop,
                             args=(_sampler_stop,), daemon=True,
                             name="device-sampler")
        t.start()


def uninstall() -> None:
    """Stop the sampler thread (tests)."""
    global _sampler_stop
    with _sampler_lock:
        if _sampler_stop is not None:
            _sampler_stop.set()
            _sampler_stop = None


def _sampler_loop(stop: threading.Event) -> None:
    while not stop.wait(_sample_period()):
        try:
            sample_once()
        except Exception:
            pass  # one bad tick must not kill the plane


# ---------------------------------------------------- compile tracking

_listener_installed = False
_listener_lock = threading.Lock()

# What jax times of a jitted function's first call
# (``dispatch.log_elapsed_time``: a scalar when the phase opens, a
# duration and a time span when it closes, all on the calling thread),
# and the fetch from the persistent cache, which fires inside the
# backend's phase.
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_FETCH_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_SPAN_OF_EVENT = {_TRACE_EVENT: "xla_trace", _LOWER_EVENT: "xla_lower",
                  _COMPILE_EVENT: "xla_compile"}
# Per thread: how many traces and lowerings are open (a jitted function
# called inside another's trace fires events of its own, and so does
# every jnp function a lowering rule traces: hundreds a program, all
# inside the phase that is the span), and the cache fetch heard since the
# last backend phase closed.
_heard = threading.local()


def install_compile_listener() -> None:
    """Register the jax.monitoring listeners once per process (after
    the first call: one flag test).  Touches no backend, so a start
    path calls it at entry (``LLMServer.__init__``, ``JaxTrainer.fit``)
    and hears its own compilations; the sampler's tick is the fallback.
    jax offers no unregister, so the callbacks gate on ``_enabled``
    (disable() must be a true no-op)."""
    global _listener_installed
    if _listener_installed:
        return
    with _listener_lock:
        if _listener_installed:
            return
        jax = sys.modules.get("jax")
        if jax is None:
            return
        try:
            from jax import monitoring
        except Exception:
            return
        monitoring.register_scalar_listener(_on_xla_phase_open)
        monitoring.register_event_duration_secs_listener(_on_xla_event)
        monitoring.register_event_time_span_listener(_on_xla_span)
        _listener_installed = True


def _on_xla_phase_open(name: str, _start: float, **_kw) -> None:
    if name == _TRACE_EVENT or name == _LOWER_EVENT:
        _heard.open = getattr(_heard, "open", 0) + 1


def _on_xla_event(name: str, duration_s: float, **_kw) -> None:
    """One jax.monitoring duration event: the backend's compilations
    counted and timed, and the cache's answer kept for the span."""
    if not _enabled:
        return
    try:
        if name == _COMPILE_EVENT:
            m = _device_metrics()
            m["xla_compiles"].inc(tags={"kind": "backend_compile"})
            m["xla_compile_seconds"].observe(float(duration_s))
        elif name == _CACHE_FETCH_EVENT:
            _heard.cache_fetch_s = float(duration_s)
            _device_metrics()["xla_phase_seconds"].observe(
                float(duration_s), tags={"phase": "cache_fetch"})
    except Exception:
        pass  # telemetry must never break a compile


def _on_xla_span(name: str, start: float, end: float, **kw) -> None:
    """One phase closed, with jax's own ``time.time()`` readings of its
    start and end: one timeline span (``xla_trace`` / ``xla_lower`` /
    ``xla_compile``), and the host phases' series."""
    span = _SPAN_OF_EVENT.get(name)
    if span is None:
        return
    if span != "xla_compile":
        _heard.open = still_open = max(0, getattr(_heard, "open", 1) - 1)
        if still_open:
            return   # inside a trace or a lowering, which is the span
    if not _enabled:
        return
    try:
        args: Dict[str, Any] = {"duration_s": round(end - start, 4),
                                "fun_name": kw.get("fun_name")}
        if span == "xla_compile":
            fetch_s = getattr(_heard, "cache_fetch_s", None)
            _heard.cache_fetch_s = None
            args["cache_hit"] = fetch_s is not None
            args["cache_fetch_s"] = round(fetch_s or 0.0, 4)
        else:
            _device_metrics()["xla_phase_seconds"].observe(
                end - start, tags={"phase": span[len("xla_"):]})
        from . import tracing

        if not tracing.enabled():
            return
        from .timeline import process_pid, record_span

        ctx = tracing.current()
        if ctx is not None:
            args["trace_id"] = ctx[0]
            if ctx[1]:
                args["parent_span_id"] = ctx[1]
        record_span(span, start, end, pid=process_pid(),
                    tid="xla-compile", args=args)
    except Exception:
        pass  # telemetry must never break a compile


# ------------------------------------------------ device-trace capture

_capture_lock = threading.Lock()


def capture_device_trace(duration_s: float = 1.0,
                         tmp_root: Optional[str] = None
                         ) -> Dict[str, Any]:
    """Capture a device profile of THIS process for ``duration_s``:
    ``jax.profiler.start_trace`` → sleep → ``stop_trace``, then zip
    the TensorBoard-loadable output directory into one artifact.
    Returns ``{name, data (zip bytes), files, duration_s, trace_id}``.
    Serialized per process — jax allows one active trace.  With tracing
    enabled the bundle also holds ``scopes.json``, :func:`program_scopes`
    of the programs this process registered: what joins the trace's
    ``%fusion.N`` events to the program's own names
    (docs/observability.md, "device seconds by scope")."""
    import shutil
    import tempfile
    import zipfile

    import jax  # explicit request: importing here is the point

    from . import tracing

    duration_s = min(max(float(duration_s), 0.05), 60.0)
    ctx = tracing.current()
    trace_id = ctx[0] if ctx else None
    with _capture_lock:
        out_dir = tempfile.mkdtemp(prefix="ray_tpu_devtrace_",
                                   dir=tmp_root)
        try:
            jax.profiler.start_trace(out_dir)
            try:
                # The sleep IS the capture window, and the lock exists
                # exactly to serialize it: jax allows one active trace
                # per process, and nothing else ever takes this lock.
                time.sleep(duration_s)  # raylint: disable=blocking-under-lock -- dedicated capture lock; the bounded sleep is the capture window itself
            finally:
                jax.profiler.stop_trace()
            buf = io.BytesIO()
            files = 0
            with zipfile.ZipFile(buf, "w",
                                 zipfile.ZIP_DEFLATED) as zf:
                for root, _dirs, names in os.walk(out_dir):
                    for fname in sorted(names):
                        path = os.path.join(root, fname)
                        zf.write(path, os.path.relpath(path, out_dir))
                        files += 1
        finally:
            shutil.rmtree(out_dir, ignore_errors=True)
    if tracing.enabled():
        # After stop_trace and outside the capture lock: this lowers
        # every registered program once (a cache fetch each).
        import json

        with zipfile.ZipFile(buf, "a", zipfile.ZIP_DEFLATED) as zf:
            zf.writestr("scopes.json", json.dumps(program_scopes()))
        files += 1
    name = "device-trace-%d-%d.zip" % (os.getpid(),
                                       int(time.time() * 1000))
    return {"name": name, "data": buf.getvalue(), "files": files,
            "duration_s": duration_s, "trace_id": trace_id}


_NULL_CTX = contextlib.nullcontext()


def annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` named ``name#trace=<id>,t=<s>``
    (``name#t=<s>`` outside a trace): the ambient trace id, so
    device-trace slices correlate with the cluster timeline, and the
    host clock reading of its opening (``timeline.now()``, wall-clock
    seconds) — the profiler's clock is session-relative, so the
    difference between an annotation event's start and its ``t`` places
    every timeline span against the device's module events of the same
    trace.  A shared no-op context when the plane is disabled or jax is
    not loaded; the plain name when tracing is off.  Cheap enough for
    per-chunk hot loops: one clock read + one string format."""
    if not _enabled:
        return _NULL_CTX
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL_CTX
    try:
        from . import timeline, tracing

        if tracing.enabled():
            ctx = tracing.current()
            head = f"trace={ctx[0]}," if ctx is not None else ""
            name = f"{name}#{head}t={timeline.now():.6f}"
        return jax.profiler.TraceAnnotation(name)
    except Exception:
        return _NULL_CTX


# ------------------------------------------------- device seconds by scope

# THE vocabulary: every ``jax.named_scope`` of the model code that a
# metric is defined on (models/llama.py, llama_serve.py, moe.py,
# mamba2.py, ops/*; docs/observability.md has the table of where each
# is).  The innermost of these words in an instruction's ``op_name`` is
# the instruction's scope.
SCOPES = (
    "layer_scan", "embed", "qkv_proj",
    "attention", "flash_attention.fwd", "flash_attention.dq",
    "flash_attention.dkdv", "decode_attention",
    "kv_write", "attn_out", "ffn",
    "router", "expert_dispatch", "expert_ffn", "shared_expert",
    "latent_proj", "router_balance",
    "mla_absorb", "mla_expand", "mla_decode_attention",
    "ssm_proj", "ssm_conv", "ssm_scan", "ssm_state_update", "ssm_out",
    "conv_proj", "short_conv", "conv_out",
    "indexer", "index_select", "sparse_attention",
    "mamba1_scan", "mamba1_state_update", "gmu", "cross_attention",
    "diff_combine",
    "kda_chunk", "kda_state_update", "kda_gates",
    "power_gate", "power_chunk", "power_state_update",
    "head", "sample", "head_loss", "optimizer",
)
PHASES = ("forward", "backward", "remat")
# What XLA lowers with a kernel of its own it also names itself, op_name
# and all, so no scope reaches it.  The one such primitive the models
# call is ``jax.lax.ragged_dot`` (``moe.moe_ffn_dropless``, under
# ``expert_ffn``): the grouped matmul, and the small kernel that turns
# the group sizes into its grid.
_COMPILER_NAMED = {"ragged-dot-none": "expert_ffn",
                   "ragged-dot-metadata": "expert_dispatch"}
_SCOPE_OF_WORD = {**{word: word for word in SCOPES}, **_COMPILER_NAMED}
# Words that keep what is traced inside them, whatever word lies further
# in: a model's name for one USE of a kernel or function that carries a
# word of its own (a decoder-hybrid-decoder's reads of its one full-length
# K/V pool go through ``decode_attention`` and ``attention`` as every
# other read does, and the kernel keeps its one name).
_OUTER_WINS = frozenset(("cross_attention",))
# ``jvp(ffn)``, ``transpose(jvp(ffn))`` -> ``ffn``: a transformation
# wraps the one name-stack element inside it.
_WRAPPED = re.compile(r"^(?:[\w.\-]+\()*([^()]*)\)*$")


def scope_of(op_name: Optional[str]):
    """``(scope | None, phase)`` of an instruction's ``op_name``
    (``jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/
    rematted_computation/ffn/dot_general``): the innermost element of
    the path that is a word of :data:`SCOPES`, through any
    ``jvp(...)`` / ``transpose(...)`` around it -- or, where the path
    holds one, the word that keeps what lies inside it
    (``cross_attention/decode_attention`` -> ``cross_attention``); the
    phase ``remat``
    where the path holds ``rematted_computation``, else ``backward``
    where it holds ``transpose(``, else ``forward``.  Pure string
    work."""
    path = op_name or ""
    if "rematted_computation" in path:
        phase = "remat"
    elif "transpose(" in path:
        phase = "backward"
    else:
        phase = "forward"
    innermost = None
    for element in reversed(path.split("/")):
        m = _WRAPPED.match(element)
        word = _SCOPE_OF_WORD.get(m.group(1)) if m else None
        if word in _OUTER_WINS:
            return word, phase
        innermost = innermost or word
    return innermost, phase


# One instruction of a compiled module's text:
#   [ROOT ]%name = <shape> opcode(operands...), attributes
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[^ ]+) = (.*?) ([a-z][a-z\-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=(%[^ ,)]+)")
_TO_APPLY = re.compile(r"\bto_apply=(%[^ ,)]+)")
# Computations whose instructions run as events of their own, under the
# instruction that names them: a loop's, a conditional's branches.
_RUNS = re.compile(r"\b(?:body|condition|true_computation|"
                   r"false_computation)=(%[^ ,)]+)"
                   r"|\bbranch_computations=\{([^}]*)\}")
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[^ ]+) \(.*\{\s*$")
# Instructions no device event stands for.
_NOT_EXECUTED = frozenset(("parameter", "constant", "get-tuple-element",
                           "tuple", "bitcast"))


def instruction_key(text: str) -> Optional[str]:
    """``%fusion.362 fusion (f32[960], bf16[8,2048,960])`` from an
    instruction's text: its name, opcode and result shape without
    layouts.  What a compiled module's text and a device trace's event
    name agree on: the trace prints the operands with their shapes and
    the compiled text without, and neither the metadata nor the backend
    config reaches the trace, so the whole text is no key; the name is
    unique within a module, and the shape keeps two programs under one
    module name (``jit_prefill`` at two buckets) apart."""
    m = _INSTRUCTION.match(text)
    if not m:
        return None
    return f"{m.group(1)} {m.group(3)} {_LAYOUT.sub('', m.group(2))}"


def scopes_of_text(hlo_text: str) -> Dict[str, List[str]]:
    """``{instruction_key: [scope or "unscoped", phase]}`` for every
    instruction of a compiled module's text that a device event can
    stand for (those of fused computations and reducers are left out:
    the fusion is the event).  A fusion whose own ``op_name`` holds no
    word of the vocabulary takes the scope that most of its fused
    computation's instructions carry; what then has none takes the
    scope of the loop, call or conditional it runs under (the compiler
    expands a scatter into a ``while`` that keeps the scatter's
    ``op_name`` over a body that has none)."""
    computations: Dict[str, List[str]] = {}
    current: Optional[List[str]] = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = computations.setdefault(m.group(1), [])
        elif line.startswith("}"):
            current = None
        elif current is not None:
            current.append(line)
    inner = set()   # fused computations and reducers: not events
    for lines in computations.values():
        for line in lines:
            inner.update(_CALLS.findall(line))
            if " call(" not in line:
                inner.update(_TO_APPLY.findall(line))

    def own(line):
        m = _OP_NAME.search(line)
        return scope_of(m.group(1) if m else None)

    def majority(name):
        votes: Dict[tuple, int] = {}
        for line in computations.get(name, ()):
            scope, phase = own(line)
            if scope is not None:
                votes[scope, phase] = votes.get((scope, phase), 0) + 1
        return max(votes, key=votes.get) if votes else None

    out: Dict[str, List[str]] = {}
    under: Dict[str, tuple] = {}    # computation -> what it runs under
    # The text defines a computation before its caller: callers first.
    for name in reversed(list(computations)):
        if name in inner:
            continue
        for line in computations[name]:
            m = _INSTRUCTION.match(line)
            if not m or m.group(3) in _NOT_EXECUTED:
                continue
            scope, phase = own(line)
            if scope is None and m.group(3) == "fusion":
                fused = _CALLS.search(line)
                voted = majority(fused.group(1)) if fused else None
                if voted is not None:
                    scope, phase = voted
            if scope is None and name in under:
                scope, phase = under[name]
            out[instruction_key(line)] = [scope or "unscoped", phase]
            if scope is not None:
                runs = [n for one, many in _RUNS.findall(line)
                        for n in [one, *re.findall(r"%[^ ,]+", many)] if n]
                if m.group(3) == "call":
                    runs += _TO_APPLY.findall(line)
                for callee in runs:
                    under.setdefault(callee, (scope, phase))
    return out


class _Program:
    """A registered program: what lowers it again, then what that gave."""

    __slots__ = ("name", "lower", "module", "scopes")

    def __init__(self, name: str, lower):
        self.name = name
        self.lower = lower     # () -> compiled text; dropped once called
        self.module: Optional[str] = None
        self.scopes: Optional[Dict[str, List[str]]] = None


_PROGRAMS_MAX = 256
_programs_lock = threading.Lock()
_programs: Dict[Any, _Program] = {}    # by (name, program, shapes)


def register_program(name: str, jitted, args, **static) -> None:
    """Remember a jitted hot-path program with the SHAPES of one call,
    for :func:`program_scopes`: every array of ``args`` as a
    ``ShapeDtypeStruct`` with its sharding (no array is kept alive),
    the static keyword arguments as they are, and the mesh and sharding
    rules ambient at this call (a train step's trace reads
    ``current_mesh()``).  Lowers nothing.  A no-op with tracing
    disabled; its callers (``LLMServer._warmup``, the train step's
    first dispatch) do not even call it then.  Must never raise."""
    from . import tracing

    if not tracing.enabled():
        return
    try:
        import jax

        from ..parallel.sharding import (current_mesh, current_rules,
                                         use_mesh, use_sharding_rules)

        def shape(x):
            if isinstance(x, jax.Array):
                # An uncommitted array lowers as a shape alone does; its
                # default device spelled out would be another module
                # text, and a miss in the compile cache.
                return jax.ShapeDtypeStruct(
                    x.shape, x.dtype, weak_type=x.weak_type,
                    sharding=x.sharding if x.committed else None)
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        shapes = jax.tree.map(shape, args)
        leaves, treedef = jax.tree.flatten(shapes)
        key = (name, id(jitted), treedef, tuple(
            (x.shape, str(x.dtype), x.sharding)
            if isinstance(x, jax.ShapeDtypeStruct) else x
            for x in leaves), tuple(sorted(static.items())))
        mesh, rules = current_mesh(), current_rules()

        def lower() -> str:
            with use_mesh(mesh), use_sharding_rules(rules):
                return jitted.lower(*shapes, **static).compile(
                    ).as_text() or ""

        with _programs_lock:
            if key in _programs:
                return
            while len(_programs) >= _PROGRAMS_MAX:
                _programs.pop(next(iter(_programs)))
            _programs[key] = _Program(name, lower)
    except Exception:
        pass  # telemetry must never break a launch


def registered_programs() -> List[str]:
    """Names of the registered programs, one per registered shape."""
    with _programs_lock:
        return [program.name for program in _programs.values()]


def clear_programs() -> None:
    """Forget every registered program (tests)."""
    with _programs_lock:
        _programs.clear()


def program_scopes() -> Dict[str, Dict[str, List[str]]]:
    """``{module name: {instruction_key: [scope, phase]}}`` of every
    registered program (programs under one module name, ``jit_prefill``
    at each bucket, share one table; the first registered wins a key
    two of them give).  Each program is lowered and compiled from its
    registered shapes ONCE -- the module text is the one that ran, so
    jax's own executable cache answers in a process that still holds
    it (0.06-1.4 s for the 1-18 programs of a benchmark cell, measured
    on a v5e), the persistent compile cache otherwise -- and what
    lowered it is dropped; later calls return what the first read.  For
    after a capture or a benchmark window, never for a launch, harvest
    or start path."""
    with _programs_lock:
        programs = list(_programs.values())
    out: Dict[str, Dict[str, List[str]]] = {}
    for program in programs:
        if program.scopes is None:
            try:
                text = program.lower()
            except Exception as e:  # noqa: BLE001
                import logging

                logging.getLogger(__name__).warning(
                    "program_scopes: %s does not lower again: %s",
                    program.name, e)
                text = ""
            m = re.match(r"HloModule ([^ ,]+)", text)
            program.module = m.group(1) if m else program.name
            program.scopes = scopes_of_text(text)
            program.lower = None
        table = out.setdefault(program.module, {})
        for key, value in program.scopes.items():
            table.setdefault(key, value)
    return out


# ---------------------------------------------------- model-plane emit

def record_train_step(tokens: int, step_s: float,
                      n_params: Optional[int] = None,
                      device_kind: Optional[str] = None,
                      n_devices: int = 1) -> None:
    """Publish one training step's model-plane gauges: tokens/s
    always, MFU when the chip roofline is known (6N dense-LM
    approximation).  ``tokens`` is the WHOLE step's token count, so
    a multi-chip gang must pass its ``device_kind`` and distinct
    chip count — the roofline denominator is per chip, and
    resolving it from THIS process's devices would be the driver's
    CPU, not the gang's accelerators.  Called from the train hot
    loop; must never raise."""
    if not _enabled or step_s <= 0:
        return
    try:
        m = model_plane_metrics()
        tps = tokens / step_s
        m["train_tokens_per_s"].set(tps)
        m["train_step_seconds"].set(step_s)
        if device_kind is None:
            jax = initialized_jax()
            if jax is not None:
                device_kind = jax.local_devices()[0].device_kind
        if n_params and device_kind:
            peak = peak_bf16_flops(device_kind)
            if peak:
                m["train_mfu"].set(
                    tps * 6 * n_params / (peak * max(1, n_devices)))
    except Exception:
        pass


def record_expert_balance(expert_rows, router_bias_max=None,
                          dispatch_compact_share=None) -> None:
    """Publish a training step's expert load: ``expert_rows`` (expert
    layers, experts), the step metric of that name
    (``llama.make_train_step``), and, where the step reports them, the
    selection biases' largest magnitude and the share of expert layers
    dispatched in one block (the step metrics of those names).
    Reading the metrics waits for the step, so a train loop calls this
    where it reads the loss.  Must never raise."""
    if not _enabled:
        return
    try:
        import numpy as np

        rows = np.asarray(expert_rows, np.float64)
        m = model_plane_metrics()
        m["train_expert_load_imbalance"].set(float(
            (rows.max(-1) / np.maximum(rows.mean(-1), 1e-9)).max()))
        if router_bias_max is not None:
            m["train_router_bias_max"].set(float(router_bias_max))
        if dispatch_compact_share is not None:
            m["train_dispatch_compact_share"].set(
                float(dispatch_compact_share))
    except Exception:
        pass


def record_program_ema(deployment: str, program: str,
                       seconds: Optional[float]) -> None:
    """Publish a serve-engine per-program execution-time EMA gauge
    (prefill / decode_chunk / spec_round).  Must never raise."""
    if not _enabled or seconds is None:
        return
    try:
        model_plane_metrics()["program_ema"].set(
            float(seconds), tags={"deployment": deployment,
                                  "program": program})
    except Exception:
        pass

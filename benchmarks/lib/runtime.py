"""What every kind of run needs from the machine: the devices and their
peaks, the placed compile cache, a count of compilations, memory peaks,
and the profiler around a slice of the window."""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, List, Optional

from . import spec


class BenchmarkRefused(Exception):
    """This machine or tree cannot run the cell: exit non-zero, print no
    result."""


@dataclasses.dataclass
class Context:
    """What ``run.py`` hands a kind's ``run(ctx)``."""
    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float                 # perf_counter at process start
    devices: List[Any]               # the cell's chips
    peaks: Dict[str, Any]            # peaks.json row of their kind
    out_dir: str

    def since_start(self) -> float:
        return time.perf_counter() - self.t_process


def load_peaks(device_kind: str,
               bench_dir: str = spec.BENCH_DIR) -> Dict[str, Any]:
    with open(os.path.join(bench_dir, "lib", "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise BenchmarkRefused(
            f"device kind {device_kind!r} is not in benchmarks/lib/"
            f"peaks.json; a chip without published peaks is an error, "
            f"not a default")
    return table[device_kind]


def place_caches() -> str:
    """The persistent compile cache at the program's fixed place inside
    the checkout (or where JAX_COMPILATION_CACHE_DIR says), keeping every
    program however quick its compile: a serve warm-up is dozens of
    sub-second programs."""
    from ray_tpu.compile_cache import place_compile_cache

    placed = place_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return placed or ""


def claim_devices(chips: int, allow_platforms=("tpu",)):
    """The cell's chips, or refuse: no accelerator, too few chips, or a
    chip whose peaks are unknown."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform not in allow_platforms:
        raise BenchmarkRefused(
            f"platform is {platform!r}; the benchmark measures on "
            f"{'/'.join(allow_platforms)} only")
    if len(devices) < chips:
        raise BenchmarkRefused(
            f"the cell asks for {chips} chip(s), jax sees {len(devices)}")
    return devices[:chips]


class CompileWatch:
    """XLA backend compilations of this process (cache fetches included:
    the event wraps the lookup), from ``jax.monitoring`` — the
    benchmark's own count, beside the program's counter."""

    def __init__(self):
        from jax import monitoring

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_secs)
        monitoring.register_event_listener(self._on_event)

    def _on_secs(self, name: str, secs: float, **_kw) -> None:
        if name.endswith("backend_compile_duration"):
            self.count += 1
            self.seconds += float(secs)

    def _on_event(self, name: str, **_kw) -> None:
        if name.endswith("/compilation_cache/cache_hits"):
            self.cache_hits += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.cache_misses += 1


def program_counters() -> Dict[str, float]:
    """The program's own XLA compile counter and seconds
    (``ray_tpu_xla_compiles_total`` / ``ray_tpu_xla_compile_seconds``)."""
    from ray_tpu.observability import device as device_plane
    from ray_tpu.observability.metrics import metrics_summary

    device_plane.sample_once()   # installs the listener if it is not yet
    summary = metrics_summary()
    return {
        "xla_compiles": float(summary.get(
            "ray_tpu_xla_compiles_total", {}).get("backend_compile", 0.0)),
        "xla_compile_seconds": float(sum(summary.get(
            "ray_tpu_xla_compile_seconds", {}).values())),
    }


def memory_peaks(devices) -> Dict[str, int]:
    """Peak bytes on the fullest chip since the process started.  On the
    TPU ``peak_bytes_in_use`` holds the program's arrays and
    ``peak_bytes_reserved`` XLA's scratch for a running program: reported
    side by side, not summed."""
    in_use = reserved = limit = 0
    for dev in devices:
        stats = dev.memory_stats() or {}
        in_use = max(in_use, int(stats.get("peak_bytes_in_use", 0)))
        reserved = max(reserved, int(stats.get("peak_bytes_reserved", 0)))
        limit = max(limit, int(stats.get("bytes_limit", 0)))
    return {"peak_in_use": in_use, "peak_reserved": reserved,
            "limit": limit}


class Tracer:
    """The profiler around a slice of the window, when ``--trace 1``."""

    def __init__(self, enabled: bool, out_dir: str):
        self.enabled = enabled
        self.dir = os.path.join(out_dir, "trace")
        self.running = False
        self.t_start = self.t_stop = None

    def start(self) -> None:
        if not self.enabled or self.running:
            return
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # annotations, not every call
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.running = True
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if not self.running:
            return
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.running = False



def read_trace(ctx: Context):
    """The traced run's trace as ``trace_reduce.Trace``; None untraced."""
    if not ctx.trace:
        return None
    from . import trace_reduce

    return trace_reduce.read(os.path.join(ctx.out_dir, "trace"))


def percentile(values, q: float) -> Optional[float]:
    import numpy as np

    values = [v for v in values if v is not None]
    if not values:
        return None
    return float(np.percentile(np.asarray(values, float), q))


_WATCH: Optional[CompileWatch] = None


def compile_watch() -> CompileWatch:
    """The process's one compile listener (``jax.monitoring`` listeners
    cannot be taken off again, so there is one, made at start-up)."""
    global _WATCH
    if _WATCH is None:
        _WATCH = CompileWatch()
    return _WATCH

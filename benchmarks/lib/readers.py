"""The arithmetic behind the metric readers in ``benchmarks/metrics/``.
A reader gets the observations of one run (what a kind's ``run``
returned, plus ``cell``, ``peaks`` and ``chips``) and returns a number,
or None where there is nothing to read (the metric is then left out)."""

from __future__ import annotations

import statistics
from typing import List, Optional

from . import flops, spec
from .runtime import percentile

FAILED_REQUEST_MS = 120_000.0   # a failed request counts as the worst

# How the device trace names things (looked at by hand, PERF.md section 3).
TRAIN_STEP_MODULE = r"^jit_step\b"
DECODE_MODULE = r"^jit_decode_k\b"
PREFILL_MODULE = r"^jit_prefill\b"


# ------------------------------------------------------------------ train
def train_tokens_per_s_per_chip(obs) -> Optional[float]:
    groups = obs.get("groups")
    if not groups:
        return None
    seconds = sum(g["t_end"] - g["t_start"] for g in groups)
    tokens = sum(g["steps"] for g in groups) * obs["tokens_per_step"]
    return tokens / seconds / obs["chips"]


def train_counts(cell):
    """The module that counts what a TRAIN step of the configuration
    needs (``train_flops_per_token``, ``flash_train_flops``,
    ``flash_train_bytes``): the one of ``benchmarks/lib/`` its file names
    under ``train_counts``, else ``lib/flops.py`` (a dense decoder, every
    layer's attention the whole causal square)."""
    name = cell.config.get("train_counts")
    if not name:
        return flops
    counts = spec.load_module("lib", name, cell.bench_dir)
    if counts is None:
        raise spec.SpecError(f"{cell.name}: no benchmarks/lib/{name}.py")
    return counts


def train_mfu(obs) -> Optional[float]:
    rate = train_tokens_per_s_per_chip(obs)
    if rate is None:
        return None
    per_token = train_counts(obs["cell"]).train_flops_per_token(
        obs["cell"].config, obs["seq_len"])
    return 100.0 * rate * per_token / obs["peaks"]["bf16_flops_per_s"]


def _step_runs(obs):
    trace = obs.get("trace")
    return trace.module_runs(TRAIN_STEP_MODULE) if trace else []


def _whole_run_ms(obs, module: str) -> Optional[float]:
    """Median device time of the module's runs that the traced slice
    holds whole (``Trace.whole_runs``: a run that the slice's edge cut
    is there with what is left of it, and is no run's time); None where
    it holds none."""
    trace = obs.get("trace")
    runs = trace.whole_runs(module) if trace else []
    if not runs:
        return None
    return 1e3 * statistics.median(e - s for s, e, _ in runs)


def train_step_device_ms(obs) -> Optional[float]:
    return _whole_run_ms(obs, TRAIN_STEP_MODULE)


def _share_of_steps(obs, seconds: float) -> Optional[float]:
    runs = _step_runs(obs)
    if not runs:
        return None
    return 100.0 * seconds / sum(e - s for s, e, _ in runs)


def kernel_s_a_step(obs, seconds: Optional[float]) -> Optional[float]:
    """Seconds a step of kernels that took ``seconds`` in the traced
    steps: their share of the steps' device time x a whole step's, so a
    step that the trace's edge cut miscounts neither."""
    step_ms = train_step_device_ms(obs)
    if not seconds or step_ms is None:
        return None
    return _share_of_steps(obs, seconds) * 1e-2 * step_ms * 1e-3


def flash_seconds(obs) -> Optional[float]:
    """Device seconds of the three flash kernels (forward, dq, dk/dv), by
    the names ``lib/flash_names.py`` reads: a step with other Mosaic
    kernels (an expert layer's grouped matmuls) counts these alone."""
    from . import flash_names

    trace = obs.get("trace")
    if not trace:
        return None
    return sum(flash_names.kernel_seconds(trace, kernel)
               for kernel in flash_names.KERNEL_OPS) or None


def flash_attention_roofline(obs) -> Optional[float]:
    """Least time the chip could take for what the kernels of one step
    must do on ONE chip (the larger of FLOPs / peak and bytes / peak, by
    the counts of the module the configuration's file names:
    ``train_counts``), over the kernels' measured time per step."""
    kernel_s = kernel_s_a_step(obs, flash_seconds(obs))
    if kernel_s is None:
        return None
    cfg, peaks, chips = obs["cell"].config, obs["peaks"], obs["chips"]
    counts = train_counts(obs["cell"])
    need_flops = counts.flash_train_flops(cfg, obs["batch"],
                                          obs["seq_len"]) / chips
    need_bytes = counts.flash_train_bytes(cfg, obs["batch"],
                                          obs["seq_len"]) / chips
    least = max(need_flops / peaks["bf16_flops_per_s"],
                need_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s


def collective_time_share(obs) -> Optional[float]:
    trace = obs.get("trace")
    if not trace or obs["chips"] < 2:
        return None
    return _share_of_steps(obs, trace.collective_seconds()[0])


def collective_exposed_share(obs) -> Optional[float]:
    trace = obs.get("trace")
    if not trace or obs["chips"] < 2:
        return None
    return _share_of_steps(obs, trace.collective_seconds()[1])


def train_input_wait_share(obs) -> Optional[float]:
    if "input_wait_s" not in obs:
        return None
    return 100.0 * obs["input_wait_s"] / (obs["t_close"] - obs["t_open"])


# ------------------------------------------------------------------ serve
def serve_output_tokens_per_s(obs) -> Optional[float]:
    log = obs.get("log")
    if log is None:
        return None
    return log.tokens_in_window() / log.seconds


def ttft_ms(obs) -> List[float]:
    """Per measured request: how late it was sent plus the engine's own
    time to the first token; a failed request counts as the worst."""
    out = []
    for r in obs.get("measured", []):
        if not r.ok:
            out.append(FAILED_REQUEST_MS)
            continue
        late = (r.sent - r.due) * 1e3 if r.due is not None else 0.0
        out.append(late + r.ttft_ms)
    return out


def tpot_ms(obs) -> List[float]:
    """Per measured request of >= 2 tokens: (completion - first token) /
    (tokens - 1)."""
    out = []
    for r in obs.get("measured", []):
        if r.ok and r.got_tokens >= 2:
            first = r.sent + r.ttft_ms * 1e-3
            out.append((r.done - first) * 1e3 / (r.got_tokens - 1))
    return out


def loadgen_lag_ms(obs) -> List[float]:
    return [(r.sent - r.due) * 1e3 for r in obs.get("measured", [])
            if r.due is not None]


def ttft_percentile(q):
    return lambda obs: percentile(ttft_ms(obs), q)


def tpot_percentile(q):
    return lambda obs: percentile(tpot_ms(obs), q)


def decode_step_device_ms(obs) -> Optional[float]:
    """A whole ``jit_decode_k`` launch's device time / its steps, the
    median over the launches the traced slice holds whole: what every
    decode roofline here divides by."""
    launch_ms = _whole_run_ms(obs, DECODE_MODULE)
    return None if launch_ms is None else launch_ms / obs["decode_chunk"]


def prefill_device_share(obs) -> Optional[float]:
    trace = obs.get("trace")
    if not trace or not trace.busy_s:
        return None
    runs = trace.module_runs(PREFILL_MODULE)
    return 100.0 * sum(e - s for s, e, _ in runs) / trace.busy_s


def context_in_flight(obs, t: float):
    """(sequences decoding at time ``t``, positions they hold in all),
    from the generator's own log: a request is decoding from its first
    token to its completion, and has by then produced its tokens at an
    even rate."""
    sequences = positions = 0
    for r in obs["log"].records:
        if not (r.ok and r.got_tokens >= 2):
            continue
        first = r.sent + r.ttft_ms * 1e-3
        if first <= t < r.done:
            made = 1 + (t - first) / (r.done - first) * (r.got_tokens - 1)
            sequences += 1
            positions += r.prompt_tokens + made
    return sequences, positions


def decode_step_roofline(obs) -> Optional[float]:
    """Least time of one decode step / the measured time of a step.  The
    least time is the configuration's: its file names, under
    ``roofline``, the module of ``benchmarks/lib/`` whose
    ``decode_step_least_s(obs)`` counts what a step of ITS layers must
    read and compute at the batch in flight at the middle of the traced
    span (HBM bytes or FLOPs at peak, the larger), beside its operation
    and byte counts."""
    cell = obs["cell"]
    step_ms = decode_step_device_ms(obs)
    if step_ms is None or not cell.config.get("roofline"):
        return None
    floor = spec.load_module("lib", cell.config["roofline"], cell.bench_dir)
    if floor is None:
        raise spec.SpecError(
            f"{cell.name}: no benchmarks/lib/{cell.config['roofline']}.py")
    least = floor.decode_step_least_s(obs)
    return None if least is None else 100.0 * least / (step_ms * 1e-3)


# ------------------------------------------------------------------- both
def device_idle_share(obs) -> Optional[float]:
    trace = obs.get("trace")
    share = trace.idle_share() if trace else None
    return None if share is None else 100.0 * share


def hbm_peak_in_use_bytes(obs) -> Optional[float]:
    return float(obs["memory"]["peak_in_use"]) or None


def hbm_peak_reserved_bytes(obs) -> Optional[float]:
    return float(obs["memory"]["peak_reserved"]) or None

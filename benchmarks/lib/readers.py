"""The arithmetic behind the metric readers in ``benchmarks/metrics/``.
A reader gets the observations of one run (what a kind's ``run``
returned, plus ``cell``, ``peaks`` and ``chips``) and returns a number,
or None where there is nothing to read (the metric is then left out)."""

from __future__ import annotations

import re
import statistics
from typing import List, Optional

from . import flops
from .runtime import percentile

FAILED_REQUEST_MS = 120_000.0   # a failed request counts as the worst

# How the device trace names things (looked at by hand, PERF.md section 3).
TRAIN_STEP_MODULE = r"^jit_step\b"
DECODE_MODULE = r"^jit_decode_k\b"
PREFILL_MODULE = r"^jit_prefill\b"
# The train step's only Mosaic kernels are flash attention's (forward,
# dq, dk/dv); the trace does not carry a kernel's own name.
FLASH_KERNEL_OP = re.escape('custom_call_target="tpu_custom_call"')


# ------------------------------------------------------------------ train
def train_tokens_per_s_per_chip(obs) -> Optional[float]:
    groups = obs.get("groups")
    if not groups:
        return None
    seconds = sum(g["t_end"] - g["t_start"] for g in groups)
    tokens = sum(g["steps"] for g in groups) * obs["tokens_per_step"]
    return tokens / seconds / obs["chips"]


def train_mfu(obs) -> Optional[float]:
    rate = train_tokens_per_s_per_chip(obs)
    if rate is None:
        return None
    per_token = flops.train_flops_per_token(obs["cell"].config,
                                            obs["seq_len"])
    return 100.0 * rate * per_token / obs["peaks"]["bf16_flops_per_s"]


def _step_runs(obs):
    trace = obs.get("trace")
    return trace.module_runs(TRAIN_STEP_MODULE) if trace else []


def train_step_device_ms(obs) -> Optional[float]:
    runs = _step_runs(obs)
    if not runs:
        return None
    return 1e3 * statistics.median(e - s for s, e, _ in runs)


def _share_of_steps(obs, seconds: float) -> Optional[float]:
    runs = _step_runs(obs)
    if not runs:
        return None
    return 100.0 * seconds / sum(e - s for s, e, _ in runs)


def flash_seconds(obs) -> Optional[float]:
    trace = obs.get("trace")
    if not trace:
        return None
    s = trace.seconds_matching(FLASH_KERNEL_OP)
    return s or None


def flash_attention_time_share(obs) -> Optional[float]:
    s = flash_seconds(obs)
    return None if s is None else _share_of_steps(obs, s)


def flash_attention_roofline(obs) -> Optional[float]:
    """Least time the chip could take for what the kernels of one step
    must do on ONE chip (the larger of FLOPs / peak and bytes / peak),
    over the kernels' measured time per step."""
    s, runs = flash_seconds(obs), _step_runs(obs)
    if s is None or not runs:
        return None
    cfg, peaks, chips = obs["cell"].config, obs["peaks"], obs["chips"]
    need_flops = flops.flash_train_flops(cfg, obs["batch"],
                                         obs["seq_len"]) / chips
    need_bytes = flops.flash_train_bytes(cfg, obs["batch"],
                                         obs["seq_len"]) / chips
    least = max(need_flops / peaks["bf16_flops_per_s"],
                need_bytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / (s / len(runs))


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


def trainer_start_s(obs) -> Optional[float]:
    if "t_first_step_launch" not in obs:
        return None
    return (obs["t_first_step_launch"] - obs["t_fit"]
            - obs["compile_s_before_first_step"])


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
    trace = obs.get("trace")
    runs = trace.module_runs(DECODE_MODULE) if trace else []
    if not runs:
        return None
    return 1e3 * statistics.median(e - s for s, e, _ in runs) \
        / obs["decode_chunk"]


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
    """Least time for one decode step at the batch in flight at the
    middle of the traced span (weights once + each sequence's keys and
    values once, against HBM bandwidth; or the FLOPs against the MXU,
    whichever is larger), over the measured time of a step."""
    step_ms = decode_step_device_ms(obs)
    span = obs.get("trace_span")
    if step_ms is None or not span or span[0] is None:
        return None
    sequences, positions = context_in_flight(obs, (span[0] + span[1]) / 2)
    if not sequences:
        return None
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        flops.decode_step_bytes(cfg, positions) / peaks["hbm_bytes_per_s"],
        flops.decode_step_flops(cfg, sequences, positions)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / (step_ms * 1e-3)


# ------------------------------------------------------------------- both
def device_idle_share(obs) -> Optional[float]:
    trace = obs.get("trace")
    share = trace.idle_share() if trace else None
    return None if share is None else 100.0 * share


def hbm_peak_in_use_bytes(obs) -> Optional[float]:
    return float(obs["memory"]["peak_in_use"]) or None


def hbm_peak_reserved_bytes(obs) -> Optional[float]:
    return float(obs["memory"]["peak_reserved"]) or None
